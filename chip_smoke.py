#!/usr/bin/env python3
"""Bring-up smoke: HGNN inference and sampled serving on one TPU chip.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the partitioned arm only

One process, no children.  Every phase runs through the entry points the
``serve --hgnn`` CLI uses (``build_hgnn_infer``, ``HGNNInferEngine``,
``HGNNServeEngine``) on the published-size synthetic IMDB graph
(4278/2081/5257 nodes, 3066/2081/5257-d features) with ``HGNNConfig``
defaults (hidden 64, 8 heads, K=64).  Graph and weights come from ``--seed``.

  (a) HAN full graph, XLA path (stacked layout)
  (b) HAN full graph, Pallas ``gat_na`` kernel, without and with the fused
      NA->SA epilogue
  (c) RGCN full graph, Pallas ``segment_spmm`` kernel
  (d) HAN sampled serving: fanout 8, 32 requests, 8 slots x 4 targets
  (k) the kernel paths (a)-(d) leave out, at the same widths: streaming
      ``gat_na`` / ``segment_spmm``, ``fused_fp_na``, ``semantic_attention``,
      ``cached_gather`` (bit-exact), each against ``kernels/ref.py``
  --four-chips: HAN with 4 graph partitions on a data=4 x model=1 mesh
      (``gather_halo`` is a shard_map all-gather) against the unpartitioned
      single-device logits

Each full-graph phase is checked against a float32 reference of the same
model on the same (degree-capped) graph: the csr layout on plain
``jax.numpy``, whose dots the executor runs at ``MATMUL_PRECISION``
(``"highest"``, ``kernels/gather.py``) like every HGNN forward.  The
tolerance is ``RTOL`` times the reference's largest |logit|; phase (a) also
shows that bf16-rounded operands miss it, so a bf16 matmul cannot pass.

Prints one line per phase, then as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Exits non-zero, without that line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# |got - ref| <= RTOL * max|ref|.  f32 paths differ from the reference only
# in summation order (~1e-6 relative); one bf16 rounding of the operands
# is ~1e-3 relative.
RTOL = 1e-4
ITERS = 5


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(phase: str, **fields) -> None:
    print(" ".join([f"phase={phase}"] + [f"{k}={v}" for k, v in fields.items()]),
          flush=True)


# ---------------------------------------------------------------------------
# the float32 reference
# ---------------------------------------------------------------------------

def _edges(nbr, mask):
    """Padded ``[N, K]`` neighbor table -> csr ``(seg, idx)`` edge list."""
    nbr, mask = np.asarray(nbr), np.asarray(mask)
    rows, cols = np.nonzero(mask)
    return (jnp.asarray(rows.astype(np.int32)),
            jnp.asarray(nbr[rows, cols].astype(np.int32)))


def csr_batch(batch: dict) -> dict:
    """The csr-layout batch of a padded HAN/RGCN batch: the same edges the
    padded layout keeps after its degree cap, as flat edge lists."""
    out = {k: v for k, v in batch.items() if k not in ("nbr", "mask", "rels")}
    if "nbr" in batch:  # HAN stacked [P, N, K]
        out["edges"] = [_edges(n, m) for n, m in zip(batch["nbr"],
                                                      batch["mask"])]
    if "rels" in batch:  # RGCN per-relation [N_d, K]
        out["rels"] = {key: _edges(*rel) for key, rel in batch["rels"].items()}
    return out


class Reference:
    """float32 csr-layout forward of ``cfg``'s model on padded batches."""

    def __init__(self, cfg, full_batch: dict):
        from repro.core.models import get_model

        self.model = get_model(cfg.replace(fused=False, use_pallas=False,
                                           fuse_na_sa=False, fanout=0))
        self.params = self.model.init(jax.random.key(cfg.seed),
                                      csr_batch(full_batch))
        self.fn = jax.jit(self.model.forward)

    def __call__(self, batch: dict, params=None):
        return np.asarray(self.fn(self.params if params is None else params,
                                  csr_batch(batch)))


def max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.asarray(want, np.float64))))


def tolerance(want) -> float:
    return RTOL * float(np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def full_graph(phase: str, cfg, hg, want_kernel: bool, ref=None,
               want=None, mesh=None) -> dict:
    """Build, compile, time and check one full-graph inference phase against
    ``want``, or against ``ref`` (made from this phase's batch when None)
    applied to this phase's batch."""
    from repro.launch.serve import build_hgnn_infer
    from repro.serve.engine import HGNNInferEngine

    t0 = time.perf_counter()
    built = build_hgnn_infer(cfg, hg, mesh)
    prep_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = built.fn.lower(built.params, built.batch).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    engine = HGNNInferEngine(built.executor, built.params, built.batch,
                             fn=built.fn)
    logits = jax.block_until_ready(engine.infer())
    t0 = time.perf_counter()
    for _ in range(ITERS):
        logits = jax.block_until_ready(engine.infer())
    ms = (time.perf_counter() - t0) / ITERS * 1e3
    got = np.asarray(logits)
    if want is None:
        ref = ref or Reference(cfg, built.batch)
        want = ref(built.batch)
    err, tol = max_err(got, want), tolerance(want)
    has_kernel = "tpu_custom_call" in hlo
    emit(phase, model=f"{cfg.model}/{cfg.dataset}",
         na=built.plan.na.layout + ("+fused-sa" if built.plan.sa.fuse_epilogue
                                    else ""),
         pallas=cfg.use_pallas, logits=list(got.shape), max_abs_err=err,
         tol=tol, ms_per_iter=ms, compile_s=compile_s, prepare_s=prep_s,
         tpu_custom_call=has_kernel)
    check(got.shape == want.shape, f"{phase}: logits shape {got.shape} != "
          f"reference {want.shape}")
    check(bool(np.isfinite(got).all()), f"{phase}: non-finite logits")
    check(err <= tol, f"{phase}: max |err| {err} > tol {tol}")
    check(has_kernel == want_kernel,
          f"{phase}: tpu_custom_call in HLO is {has_kernel}, "
          f"expected {want_kernel}")
    return {"built": built, "logits": got, "hlo": hlo, "ref": ref,
            "want": want}


def bf16_sensitivity(ref: Reference, batch: dict, want) -> None:
    """The reference with every float operand rounded to bf16 must miss the
    tolerance — evidence that a bf16 matmul would be caught."""

    def round_bf16(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32:
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    params = jax.tree_util.tree_map(round_bf16, ref.params)
    feats = {t: round_bf16(f) for t, f in batch["feats"].items()}
    got = ref({**batch, "feats": feats}, params)
    err, tol = max_err(got, want), tolerance(want)
    emit("a.bf16", max_abs_err=err, tol=tol, caught=err > tol)
    check(err > tol, f"bf16-rounded operands pass the tolerance "
          f"({err} <= {tol}): RTOL is too loose")


def sampled_serving(hg, cfg) -> None:
    """(d): the CLI's serving smoke through HGNNServeEngine."""
    from repro.launch.serve import build_hgnn_infer
    from repro.serve.engine import HGNNRequest, HGNNServeEngine
    from repro.serve.resilience import OK
    from repro.serve.sampler import HGNNSampler

    slots, slot_targets, n_req = 8, 4, 32
    built = build_hgnn_infer(cfg, hg)
    ref = Reference(cfg, built.batch)
    engine = HGNNServeEngine(built.executor, built.params,
                             HGNNSampler(built.plan, cfg, hg), slots=slots,
                             slot_targets=slot_targets, fn=built.fn)
    n_t = hg.node_counts[built.plan.target]
    rng = np.random.default_rng(cfg.seed)
    reqs = [HGNNRequest(targets=rng.integers(
        0, n_t, size=int(rng.integers(1, 2 * slot_targets + 1))))
        for _ in range(n_req)]
    t0 = time.perf_counter()
    engine.warmup()
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine.serve(reqs)
    wall_s = time.perf_counter() - t0
    st = engine.stats()
    n_ok = sum(r.status == OK for r in reqs)
    finite = all(r.logits is not None and np.isfinite(r.logits).all()
                 for r in reqs)
    # one step checked row for row: a single request served in one step
    # (sorted unique ids: the engine serves a request's ids in that order)
    probe = HGNNRequest(targets=np.sort(rng.choice(n_t, slot_targets,
                                                   replace=False)))
    engine.serve([probe])
    sb = engine.last_sb
    want = ref(sb.batch)[sb.target_rows]
    err, tol = max_err(probe.logits, want), tolerance(want)
    recompiles = engine.stats()["compiles_after_warmup"]
    emit("d", model=f"{cfg.model}/{cfg.dataset}", fanout=cfg.fanout,
         requests=n_req, ok=n_ok, steps=st["steps"],
         recompiles=st["compiles_after_warmup"], finite=finite,
         probe_max_abs_err=err, tol=tol, warmup_s=warm_s,
         serve_wall_s=wall_s, step_ms=st["wall_mean_ms"])
    check(n_ok == n_req, f"d: {n_req - n_ok} of {n_req} requests not OK "
          f"({st['resilience']['statuses']})")
    check(st["compiles_after_warmup"] == 0 and recompiles == 0,
          f"d: recompiles after warmup ({recompiles})")
    check(finite, "d: non-finite served logits")
    check(probe.status == OK
          and np.array_equal(probe.served, sb.target_ids),
          "d: probe request not served in one step")
    check(err <= tol, f"d: probe max |err| {err} > tol {tol}")


def kernel_paths(seed: int) -> None:
    """(k): kernels and paths that (a)-(d) do not reach, at IMDB widths."""
    from repro.kernels import (feature_cache, fused_fp_na, gat_na, ref,
                               segment_spmm, semantic_attn)
    from repro.kernels.gather import MATMUL_PRECISION

    rng = np.random.default_rng(seed)
    n, heads, dh, k, hs = 4278, 8, 8, 64, 128
    d = heads * dh

    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32)
                           * scale)

    def ids(hi, *shape):
        return jnp.asarray(rng.integers(0, hi, shape), jnp.int32)

    def fp_na_ref(x, w, nbr, mask):  # linearity keeps the oracle small
        return ref.segment_spmm(x @ w, nbr, mask)

    mask = jnp.asarray(rng.random((n, k)) < 0.5, jnp.float32)
    h = arr(n, heads, dh)
    att = {"a_dst": arr(heads, dh, scale=0.2),
           "a_src": arr(heads, dh, scale=0.2)}
    cases = {  # name: (kernel, oracle, args)
        "gat_na.streaming": (functools.partial(gat_na.gat_na, block_m=512),
                             ref.gat_na, (att, h, h, ids(n, n, k), mask)),
        "segment_spmm.streaming": (
            functools.partial(segment_spmm.segment_spmm, block_m=512),
            ref.segment_spmm, (arr(5257, d), ids(5257, n, k), mask)),
        "fused_fp_na.resident": (
            fused_fp_na.fused_fp_na, fp_na_ref,
            (arr(2081, 2081, scale=0.1), arr(2081, d, scale=0.1),
             ids(2081, n, k), mask)),
        "fused_fp_na.streaming": (
            fused_fp_na.fused_fp_na, fp_na_ref,
            (arr(n, 3066, scale=0.1), arr(3066, d, scale=0.1),
             ids(n, n, k), mask)),
        "semantic_attention": (
            semantic_attn.semantic_attention, ref.semantic_attention,
            (arr(2, n, d), arr(d, hs, scale=0.1), arr(hs), arr(hs))),
        "cached_gather": (
            feature_cache.cached_gather, ref.cached_gather,
            (arr(n, d), jnp.asarray(rng.choice(n, 1024, replace=False),
                                    jnp.int32), ids(n + 1024, n, 16))),
    }
    for name, (kernel, oracle, args) in cases.items():
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        got = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        for _ in range(ITERS):
            got = jax.block_until_ready(compiled(*args))
        ms = (time.perf_counter() - t0) / ITERS * 1e3
        with jax.default_matmul_precision(MATMUL_PRECISION):
            want = np.asarray(jax.jit(oracle)(*args))
        got = np.asarray(got)
        has_kernel = "tpu_custom_call" in compiled.as_text()
        # cached_gather moves bitwise row copies: exact, not toleranced
        tol = 0.0 if name == "cached_gather" else tolerance(want)
        err = max_err(got, want)
        emit(f"k.{name}", out=list(got.shape), max_abs_err=err, tol=tol,
             ms_per_iter=ms, compile_s=compile_s, tpu_custom_call=has_kernel)
        check(has_kernel, f"k.{name}: no tpu_custom_call in the HLO")
        check(err <= tol, f"k.{name}: max |err| {err} > tol {tol}")


def one_chip(seed: int) -> None:
    from repro.configs.base import HGNNConfig
    from repro.data.synthetic import make_dataset

    hg = make_dataset("imdb", seed=seed)
    han = HGNNConfig(model="han", dataset="imdb", fused=True, seed=seed)
    a = full_graph("a", han, hg, want_kernel=False)
    bf16_sensitivity(a["ref"], a["built"].batch, a["want"])
    full_graph("b", han.replace(use_pallas=True), hg, want_kernel=True,
               ref=a["ref"])
    full_graph("b.fused-sa", han.replace(use_pallas=True, fuse_na_sa=True),
               hg, want_kernel=True, ref=a["ref"])
    full_graph("c", HGNNConfig(model="rgcn", dataset="imdb", fused=True,
                               use_pallas=True, seed=seed),
               hg, want_kernel=True)
    sampled_serving(hg, han.replace(fanout=8))
    kernel_paths(seed)


def four_chips(seed: int) -> None:
    """HAN partitioned over a data=4 mesh against the unpartitioned logits
    on one device."""
    from repro.configs.base import HGNNConfig
    from repro.data.synthetic import make_dataset
    from repro.launch.mesh import make_smoke_mesh

    hg = make_dataset("imdb", seed=seed)
    han = HGNNConfig(model="han", dataset="imdb", fused=True, seed=seed)
    single = full_graph("4.single", han, hg, want_kernel=False)
    part = full_graph("4.partitioned", han.replace(partitions=4), hg,
                      want_kernel=False, want=single["want"],
                      mesh=make_smoke_mesh(data=4, model=1))
    built = part["built"]
    n_dev = {len(x.sharding.device_set)
             for x in jax.tree_util.tree_leaves((built.params, built.batch))
             if isinstance(x, jax.Array)}
    err = max_err(part["logits"], single["logits"])
    tol = tolerance(single["logits"])
    has_ag = "all-gather" in part["hlo"]
    emit("4.parity", vs="single-device", max_abs_err=err, tol=tol,
         devices_per_array=sorted(n_dev), all_gather=has_ag)
    check(n_dev == {4}, f"4: arrays span {sorted(n_dev)} devices, not all 4")
    check(has_ag, "4: no all-gather in the partitioned HLO")
    check(err <= tol, f"4: partitioned vs single-device |err| {err} > {tol}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned arm on a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic graph and the weights")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run it from "
              "the repository root", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 3
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: need {need} TPU devices, found {len(devs)}",
              file=sys.stderr)
        return 3
    print(f"cache_dir={enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(args.seed)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total_s={time.perf_counter() - t0}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
