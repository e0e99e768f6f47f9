"""Serving launchers: LM batched generation + stage-aware sharded HGNN inference.

LM slot engine:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --requests 8 --max-tokens 16

HGNN inference (the paper's workloads, partitioned by stage taxonomy):

  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.serve --hgnn han --dataset imdb \
      --mesh-data 2 --mesh-model 4
"""
from __future__ import annotations

import argparse
import time
from typing import Any, NamedTuple, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, tree_map_with_path

from repro.configs import get_config, get_reduced
from repro.configs.base import HGNNConfig
from repro.dist.sharding import resolve_spec, use_mesh
from repro.nn.transformer import init_lm_params
from repro.serve.engine import Request, ServeEngine


# ---------------------------------------------------------------------------
# stage-aware sharded HGNN inference
# ---------------------------------------------------------------------------


class BuiltHGNNInfer(NamedTuple):
    fn: Any      # jitted (params, batch) -> logits
    params: Any  # device_put with stage-aware shardings (if mesh given)
    batch: Any
    plan: Any = None      # the StagePlan the executor runs
    executor: Any = None  # StageGraphExecutor (characterization hooks)


def hgnn_shardings(plan, params: Any, batch: Any, mesh: Mesh):
    """Resolve a plan's declarative sharding tables into NamedShardings.

    ``plan.param_specs`` / ``plan.batch_specs`` are (key, ndim, logical-spec)
    rules (see ``repro.core.plan``): a pytree leaf whose dict path contains
    ``key`` and whose rank matches gets the resolved spec; everything else
    (attention vectors, classifier, feature pools) replicates.  The rules
    follow ``HGNN_STAGE_SPECS`` — FP weights column-sharded over 'model',
    destination-node tables over the BATCH axes, source pools replicated —
    and cover every layout (stacked, bucketed, per-relation, instance)
    without model-specific branches here.
    """
    rep = NamedSharding(mesh, P())

    def named(shape, logical):
        return NamedSharding(mesh, resolve_spec(shape, logical, mesh))

    def resolver(rules):
        def fn(path, leaf):
            keys = [k.key for k in path if isinstance(k, DictKey)]
            nd = getattr(leaf, "ndim", None)
            for key, ndim, spec in rules:
                if nd == ndim and key in keys:
                    return named(leaf.shape, spec)
            return rep
        return fn

    return (tree_map_with_path(resolver(plan.param_specs), params),
            tree_map_with_path(resolver(plan.batch_specs), batch))


def build_hgnn_infer(cfg: HGNNConfig, hg, mesh: Optional[Mesh] = None,
                     rng: Optional[jax.Array] = None) -> BuiltHGNNInfer:
    """Stage-aware sharded HGNN inference entry point — plan-driven.

    The paper's finding — FP is dense DM-Type, NA is irregular TB-Type, SA is
    EW-Type — becomes the partitioning strategy: FP shards its projection
    matmul over 'model', padded NA shards destination nodes over the batch
    axes with a replicated source pool, SA needs no resharding.  With
    ``mesh=None`` this is the plain single-device path (identical math).
    A padded NA layout is required on a mesh (``cfg.fused=True`` for
    HAN/RGCN; MAGNN's instance tables always shard).
    """
    from repro.core.models import get_model

    model = get_model(cfg)
    plan = model.plan()
    if cfg.fuse_na_sa and not plan.sa.fuse_epilogue:
        import warnings

        warnings.warn(
            f"fuse_na_sa requested but {plan.model}'s NA layout "
            f"({plan.na.layout!r}) does not support the NA→SA epilogue "
            "(stacked only); running two-pass SA", stacklevel=2)
    if mesh is not None and not plan.shards_on_mesh:
        raise ValueError(
            f"sharded HGNN inference needs a padded NA layout, but "
            f"{plan.model}'s plan resolved to 'csr' (gather/scatter cannot "
            "shard): set cfg.fused=True for HAN/RGCN; GCN has no sharded "
            "layout")
    batch = model.prepare(hg)
    params = model.init(rng if rng is not None else jax.random.key(cfg.seed),
                        batch)

    if mesh is None:
        # an async stage-graph schedule swaps the jitted monolith for the
        # overlapped dispatcher (bit-exact; per-stage jits cached on the
        # executor).  Sampled serving keeps the monolith — there the
        # schedule's overlap source is the engine's sampler prefetch
        # thread, and the serve engine diffs the jit cache for its
        # compiles_after_warmup guarantee.
        if plan.schedule is not None and plan.sample is None:
            return BuiltHGNNInfer(model.executor.forward_overlapped, params,
                                  batch, plan, model.executor)
        return BuiltHGNNInfer(jax.jit(model.forward), params, batch,
                              plan, model.executor)

    def fn(p, b):
        with use_mesh(mesh):
            return model.forward(p, b)

    p_sh, b_sh = hgnn_shardings(plan, params, batch, mesh)
    params = jax.device_put(params, p_sh)
    batch = jax.device_put(batch, b_sh)
    return BuiltHGNNInfer(jax.jit(fn), params, batch, plan, model.executor)


def build_fault_injector(args, part) -> Any:
    """``--inject-faults SEED`` -> the chaos-smoke schedule: two transient
    sampler faults + one transient forward fault (absorbed by retries), one
    persistent sampler fault (fails the step's requests), injected latency
    on three steps (drives the degradation ladder when --slo-ms is set),
    and — partitioned runs only — one partition loss at step 3 (failover
    re-partitions over the survivors).  Deterministic per seed."""
    from repro.serve.faults import FaultInjector

    return FaultInjector.seeded(
        seed=args.inject_faults, n_steps=max(args.requests, 8),
        sampler=2, forward=1, persistent_sampler=1, latency_steps=3,
        latency_s=(args.slo_ms or 50.0) / 250.0,
        partition_loss_step=3 if part is not None and part.k > 1 else None,
        partition=0)


def run_hgnn_serve(args, cfg: HGNNConfig, hg, built: BuiltHGNNInfer) -> None:
    """Request-path serving: neighbor-sampled minibatches through the
    slot-based continuous-batching engine (``--fanout >= 1``)."""
    from repro.serve.engine import HGNNRequest, HGNNServeEngine
    from repro.serve.resilience import ResilienceConfig
    from repro.serve.sampler import HGNNSampler

    sampler = HGNNSampler(built.plan, cfg, hg)
    part = built.plan.partition
    res = ResilienceConfig(max_queue=args.max_queue,
                           deadline_ms=args.deadline_ms,
                           slo_ms=args.slo_ms,
                           slo_signal=args.slo_signal)
    injector = (build_fault_injector(args, part)
                if args.inject_faults is not None else None)
    engine = HGNNServeEngine(built.executor, built.params, sampler,
                             slots=args.slots,
                             slot_targets=args.slot_targets, fn=built.fn,
                             resilience_cfg=res, injector=injector)
    n_t = hg.node_counts[built.plan.target]
    rng = np.random.default_rng(0)
    reqs = [
        HGNNRequest(targets=rng.integers(
            0, n_t, size=int(rng.integers(1, 2 * args.slot_targets + 1))))
        for _ in range(args.requests)
    ]
    n_targets = sum(len(r.targets) for r in reqs)
    t0 = time.time()
    engine.warmup()
    warm = time.time() - t0
    t0 = time.time()
    engine.serve(reqs)
    dt = time.time() - t0
    st = engine.stats()
    rungs = ";".join(f"{i}:{n}" for i, n in st["rung_hits"].items())
    print(f"serve {cfg.model}/{cfg.dataset}"
          f"{f' +partitions={part.k}' if part is not None else ''} "
          f"requests={len(reqs)} targets={n_targets} slots={args.slots} "
          f"slot_targets={args.slot_targets} fanout={cfg.fanout} "
          f"steps={st['steps']} recompiles={st['compiles_after_warmup']} "
          f"frontier_bytes={st['frontier_bytes']:.0f} "
          f"truncated={st['truncated_rows']} rung_hits={rungs} "
          f"warmup_ms={warm*1e3:.2f} wall_ms={dt*1e3:.2f} "
          f"step_ms={st['wall_mean_ms']:.3f}")
    rs = st["resilience"]
    print(f"  resilience: ok={rs['ok_requests']} "
          f"partial={rs['partial_requests']} failed={rs['failed_requests']} "
          f"rejected={rs['rejected']} shed={rs['shed']} "
          f"retries={rs['retries']} failed_steps={rs['failed_steps']} "
          f"deadline_expired={rs['deadline_expired']} "
          f"degrade_steps={rs['degrade_steps']} "
          f"max_degrade_level={rs['max_degrade_level']} "
          f"failovers={rs['partition_failovers']}")
    if rs["failed_requests"] and args.inject_faults is None:
        # with no fault schedule, a FAILED request is a real fault (compile
        # refusal, device OOM, sampler error): the run must not pass
        raise SystemExit(f"{rs['failed_requests']} of {len(reqs)} requests "
                         "FAILED with no --inject-faults schedule")
    if "prefetch" in st:
        pf = st["prefetch"]
        print(f"  prefetch: issued={pf['issued']} hits={pf['hits']} "
              f"mispredicts={pf['mispredicts']} cold={pf['cold']}")
    if "residency" in st:
        rd = st["residency"]
        print(f"  residency: cache_rows={rd['cache_rows']} "
              f"hits={rd['hits']} misses={rd['misses']} rows={rd['rows']} "
              f"hit_rate={rd['hit_rate']:.3f} evictions={rd['evictions']}")
    if args.characterize:
        sb = engine.last_sb
        recs = built.executor.stage_records(built.params, sb.batch,
                                            sample_meta=sb.meta)
        sm = recs["stages"]["SAMPLE"]
        print(f"  SAMPLE: rung={sm['rung']} n_targets={sm['n_targets']} "
              f"frontier_rows={sm['frontier_rows']} "
              f"frontier_bytes={sm['frontier_bytes']:.3g} "
              f"index_bytes={sm['index_bytes']:.3g}")
        for stage, rec in recs["stages"].items():
            if stage == "SAMPLE":
                continue
            print(f"  {stage}: flops={rec['flops']:.3g} "
                  f"hbm_bytes={rec['hbm_bytes']:.3g} "
                  f"bound={rec['roofline']['bound']}")


def run_hgnn(args) -> None:
    from repro.data.synthetic import make_dataset
    from repro.launch.mesh import make_smoke_mesh
    from repro.serve.engine import HGNNInferEngine

    if args.hgnn == "gcn" and args.dataset != "reddit":
        raise SystemExit("--hgnn gcn runs the paper's homogeneous GNN "
                         "comparison: use --dataset reddit")
    cfg = HGNNConfig(model=args.hgnn, dataset=args.dataset, fused=True,
                     use_pallas=args.use_pallas,
                     degree_buckets=args.degree_buckets,
                     fuse_na_sa=args.fuse_na_sa,
                     partitions=args.partitions,
                     layers=args.layers,
                     fanout=args.fanout,
                     cache_rows=args.cache_rows,
                     overlap=args.overlap)
    hg = make_dataset(args.dataset)
    mesh = None
    if args.mesh_data * args.mesh_model > 1:
        if args.fanout >= 1:
            raise SystemExit("--fanout serving runs single-device or "
                             "graph-partitioned (--partitions); it does not "
                             "combine with a --mesh-data/--mesh-model mesh")
        mesh = make_smoke_mesh(data=args.mesh_data, model=args.mesh_model)
    built = build_hgnn_infer(cfg, hg, mesh)
    if args.fanout >= 1:
        run_hgnn_serve(args, cfg, hg, built)
        return
    engine = HGNNInferEngine(built.executor, built.params, built.batch,
                             fn=built.fn)
    logits = jax.block_until_ready(engine.infer())
    t0 = time.time()
    for _ in range(args.iters):
        logits = jax.block_until_ready(engine.infer())
    dt = (time.time() - t0) / max(args.iters, 1)
    mesh_desc = (f"{dict(zip(mesh.axis_names, mesh.devices.shape))}"
                 if mesh else "single-device")
    na = built.plan.na
    part = built.plan.partition
    n_l = built.plan.n_layers
    print(f"{cfg.model}/{cfg.dataset} [na={na.kind}/{na.layout}"
          f"{' +fused-sa' if built.plan.sa.fuse_epilogue else ''}"
          f"{f' +partitions={part.k}' if part is not None else ''}"
          f"{f' x{n_l}layers' if n_l > 1 else ''}"
          f"{f' +overlap={cfg.overlap}' if built.plan.schedule else ''}] "
          f"logits {logits.shape} on {mesh_desc}: {dt*1e3:.2f} ms/iter")
    if built.plan.schedule is not None and mesh is None:
        ov = built.executor.overlap_record()
        d = built.executor.last_dispatch
        print(f"  overlap: depth={ov['depth']} stages={ov['stages']} "
              f"edges={ov['edges']} "
              f"concurrent_pairs={ov['concurrent_pairs']} "
              f"overlapped_stages={ov['overlapped_stages']} "
              f"max_inflight={d.get('max_inflight', 1)}")
    res = (built.batch.get("residency")
           if isinstance(built.batch, dict) else None)
    if res is not None:
        ct = res["counters"]
        print(f"  residency: cache_rows={ct['cache_rows']} "
              f"hits={ct['hits']} misses={ct['misses']} rows={ct['rows']} "
              f"hit_rate={ct['hits'] / max(ct['rows'], 1):.3f}")
    if args.characterize:
        # one stage_records call covers both the per-stage table and the
        # partition summary (lower+compile+HLO walk per stage is expensive)
        recs = built.executor.stage_records(built.params, built.batch)
        for stage, rec in recs["stages"].items():
            extra = (f" halo_bytes={rec['halo_bytes']:.3g}"
                     if "halo_bytes" in rec else "")
            print(f"  {stage}: flops={rec['flops']:.3g} "
                  f"hbm_bytes={rec['hbm_bytes']:.3g} "
                  f"bound={rec['roofline']['bound']}{extra}")
        if "partition" in recs:
            pt = recs["partition"]
            print(f"  partition: k={pt['k']} cut_ratio={pt['cut_ratio']:.3f} "
                  f"halo_rows={pt['halo_rows']:.0f} "
                  f"halo_bytes={pt['halo_bytes']:.3g} "
                  f"(x{pt['layers']} layers = "
                  f"{pt['halo_bytes_total']:.3g} total)")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slots", type=int, default=4)
    # HGNN inference mode (stage-aware sharded; see run_hgnn)
    ap.add_argument("--hgnn", default=None,
                    choices=["han", "rgcn", "magnn", "gcn"],
                    help="serve an HGNN model instead of an LM")
    ap.add_argument("--dataset", default="imdb",
                    choices=["imdb", "acm", "dblp", "reddit"])
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--use-pallas", action="store_true",
                    help="fused GAT-NA / segment-SpMM Pallas kernels "
                         "(TPU backend)")
    ap.add_argument("--degree-buckets", type=int, default=0,
                    help=">1: degree-bucketed padded NA layout "
                         "(HAN metapaths + RGCN per-relation tables)")
    ap.add_argument("--partitions", type=int, default=0,
                    help=">=1: graph-partitioned execution with that many "
                         "edge-cut partitions (per-partition FP/NA + explicit "
                         "halo feature exchange; repro.dist.partition)")
    ap.add_argument("--layers", type=int, default=1,
                    help=">1: stack that many FP->NA->SA layers (per-layer "
                         "params; the graph-side index tables are built once "
                         "and reused; partitioned runs re-exchange updated "
                         "halo features every layer)")
    ap.add_argument("--cache-rows", type=int, default=0,
                    help=">=1: hot-feature residency — keep that many "
                         "degree-ordered rows per source type resident "
                         "(repro.core.residency); NA gathers serve hot rows "
                         "from the cache section, partitioned runs skip the "
                         "halo exchange for hot rows, and serving keeps a "
                         "live per-type cache over the sampled frontier")
    ap.add_argument("--overlap", type=int, default=0,
                    help=">=1: async stage-graph schedule with that "
                         "in-flight dispatch depth — halo exchange overlaps "
                         "owned-rows NA, per-metapath NA stages dispatch "
                         "concurrently, and serving prefetches the next "
                         "step's sample while the device computes "
                         "(1 = serial-degenerate parity baseline)")
    ap.add_argument("--fanout", type=int, default=0,
                    help=">=1: request-path serving — neighbor-sampled "
                         "minibatch inference (per-hop fan-out cap) through "
                         "the slot-based continuous-batching engine; "
                         "--requests/--slots/--slot-targets size the queue")
    ap.add_argument("--slot-targets", type=int, default=4,
                    help="target vertices each slot contributes per serving "
                         "step (HGNN serving mode)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: expired requests complete "
                         "PARTIAL with the rows served so far (HGNN serving "
                         "resilience)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-step SLO target: walls breaching it drive the "
                         "degradation ladder (smaller chunks + smaller "
                         "warmed rungs; restores when pressure drops)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission bound: requests beyond this queue depth "
                         "are shed (status REJECTED) instead of growing the "
                         "backlog")
    ap.add_argument("--inject-faults", type=int, default=None,
                    help="seed a deterministic fault schedule (transient + "
                         "persistent sampler/forward faults, injected "
                         "latency, partition loss) through the serve loop — "
                         "the chaos-smoke harness")
    ap.add_argument("--slo-signal", choices=("observed", "injected"),
                    default="observed",
                    help="wall feeding the SLO comparison: 'observed' = real "
                         "step wall + injected latency (production); "
                         "'injected' = the fault schedule's latency alone — "
                         "replay-deterministic degradation for chaos smokes")
    ap.add_argument("--fuse-na-sa", action="store_true",
                    help="fused NA→SA epilogue: SA pass-1 scores accumulate "
                         "inside the NA kernel (stacked layout)")
    ap.add_argument("--characterize", action="store_true",
                    help="print the per-stage FLOPs/bytes/roofline records")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()

    if args.hgnn:
        run_hgnn(args)
        return

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve launcher covers decoder-only archs; "
                         "see examples/serve_decode.py for enc-dec")
    params = init_lm_params(jax.random.key(0), cfg)
    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         max_len=args.prompt_len + args.max_tokens)
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, args.prompt_len).astype(np.int32),
                max_tokens=args.max_tokens, temperature=args.temperature)
        for _ in range(args.requests)
    ]
    t0 = time.time()
    done = engine.generate(reqs)
    dt = time.time() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    for i, r in enumerate(done):
        print(f"req{i}: {r.out_tokens}")
    print(f"{total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/max(dt,1e-9):.1f} tok/s)")


if __name__ == "__main__":
    main()
