"""Benchmark driver: one module per paper table/figure + the LM roofline.
Prints ``name,us_per_call,derived`` CSV (benchmarks/common.py contract).

When the HGNN trajectory modules run (``bench_stage_breakdown``,
``bench_na_fused``, ``bench_sa_epilogue``, ``bench_partition``,
``bench_layers`` and/or ``bench_serving``), their rows
are also folded into
``BENCH_hgnn.json`` at the repo root — the machine-readable perf baseline
future PRs diff against (per-stage wall + characterization breakdown,
fused-vs-baseline and bucketed-vs-CSR NA speedups + launch counts, the
fused NA→SA epilogue's saved-HBM-pass snapshot, the partitioned
halo-traffic sweep, the L-layer depth sweep with per-layer stage records +
halo-bytes × L, the request-path serving sweep with its sampled frontier
traffic + ladder hit counts, and the seeded chaos sweep with its
retry/degrade/shed/failover counters).

``--check`` turns the run into a regression gate: before the new snapshot is
written, every fresh stage cost (FP/NA/SA and, for partitioned runs, the
halo-exchange stage and its cut/halo traffic) is diffed against the
committed ``BENCH_hgnn.json``; the run fails on a >20% regression (wall
times behind a small absolute floor, ``BENCH_GATE_FLOOR_US``, to absorb CI
timer noise) and fails loudly when a committed stage is missing from the
fresh run.
"""
import json
import os
import re
import sys
import time
import traceback
from pathlib import Path

MODULES = [
    "bench_stage_breakdown",     # Fig. 2
    "bench_kernel_types",        # Fig. 3
    "bench_kernel_roofline",     # Fig. 4 + Table 3
    "bench_neighbor_scaling",    # Fig. 5a
    "bench_metapath_scaling",    # Fig. 5b
    "bench_subgraph_parallelism",  # Fig. 5c
    "bench_sparsity_vs_length",  # Fig. 6a + guideline (c)
    "bench_total_vs_metapaths",  # Fig. 6b
    "bench_fusion",              # guidelines §5 before/after
    "bench_na_fused",            # fused GAT-NA vs per-head baseline
    "bench_sa_epilogue",         # fused NA->SA epilogue HBM-pass snapshot
    "bench_partition",           # partitioned execution: cut vs halo vs NA
    "bench_layers",              # L-layer depth sweep: stage mix + halo x L
    "bench_serving",             # request-path slot serving: sampled minibatch
    "bench_resilience",          # seeded chaos: retries/degrade/shed/failover
    "bench_residency",           # hot-row cache: hit-rate vs NA HBM bytes
    "bench_lm_roofline",         # 40-cell arch x shape roofline table
]

BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_hgnn.json"


def parse_breakdown(rows) -> dict:
    """``fig2/<model>/<ds>/<stage>`` wall rows -> {case: {stage: us}}."""
    out: dict = {}
    for name, us, derived in rows:
        m = re.fullmatch(r"fig2/(\w+)/(\w+)/(FP|NA|SA)", name)
        if m:
            out.setdefault(f"{m.group(1)}/{m.group(2)}", {})[
                m.group(3)] = round(us, 1)
    return out


def parse_characterization(rows) -> dict:
    """``fig2/<model>/<ds>/<stage>/char`` rows -> {case: {stage: metrics}}."""
    out: dict = {}
    for name, us, derived in rows:
        m = re.fullmatch(r"fig2/(\w+)/(\w+)/(FP|NA|SA)/char", name)
        if m:
            d = dict(kv.split("=", 1) for kv in derived.split())
            out.setdefault(f"{m.group(1)}/{m.group(2)}", {})[
                m.group(3)] = {"flops": float(d["flops"]),
                               "hbm_bytes": float(d["hbm_bytes"]),
                               "bound": d["bound"]}
    return out


def parse_partition(rows) -> dict:
    """``partition/<model>/<ds>/k<K>/<stage>`` rows ->
    {case/kK: {stage_us + cut/halo metrics}}."""
    out: dict = {}
    for name, us, derived in rows or []:
        m = re.fullmatch(r"partition/(\w+)/(\w+)/k(\d+)/(NA|gather_halo)",
                         name)
        if not m:
            continue
        rec = out.setdefault(
            f"{m.group(1)}/{m.group(2)}/k{m.group(3)}", {})
        rec[f"{m.group(4)}_us"] = round(us, 1)
        d = dict(kv.split("=", 1) for kv in derived.split())
        for key in ("cut_ratio", "halo_rows", "halo_bytes"):
            if key in d:
                rec[key] = float(d[key])
        if "cut_edges" in d:
            rec["cut_edges"] = int(d["cut_edges"])
    return out


def parse_layers(rows) -> dict:
    """``layers/<model>/<ds>/L<depth>/...`` rows -> {case: record}.

    Per case (``han/imdb/L2``): ``stages_us`` per-layer stage walls,
    ``char`` per-layer FLOPs/HBM bytes (deterministic, gated), and ``halo``
    the partitioned arm's traffic (halo-bytes × L, deterministic, gated)."""
    out: dict = {}
    for name, us, derived in rows or []:
        m = re.fullmatch(r"layers/(\w+)/(\w+)/L(\d+)/(.+)", name)
        if not m:
            continue
        case = f"{m.group(1)}/{m.group(2)}/L{m.group(3)}"
        rec = out.setdefault(case, {})
        tail = m.group(4)
        d = dict(kv.split("=", 1) for kv in derived.split()) if derived else {}
        if tail == "halo":
            rec["halo"] = {k: float(v) for k, v in d.items()}
        elif tail.startswith("char/"):
            rec.setdefault("char", {})[tail[5:]] = {
                "flops": float(d["flops"]),
                "hbm_bytes": float(d["hbm_bytes"])}
        else:
            rec.setdefault("stages_us", {})[tail] = round(us, 1)
    return out


def parse_serving(rows) -> dict:
    """``serving/<model>/<ds>/s<slots>`` rows -> {case: record}.

    ``step_us`` is the latency wall (recorded, never gated); the rest are
    deterministic serving quantities — frontier bytes, ladder hit counts,
    step/recompile counts — that ``--check`` gates."""
    out: dict = {}
    for name, us, derived in rows or []:
        m = re.fullmatch(r"serving/(\w+)/(\w+)/s(\d+)", name)
        if not m:
            continue
        d = dict(kv.split("=", 1) for kv in derived.split())
        out[f"{m.group(1)}/{m.group(2)}/s{m.group(3)}"] = {
            "step_us": round(us, 1),
            "requests": int(d["requests"]),
            "targets": int(d["targets"]),
            "steps": int(d["steps"]),
            "recompiles": int(d["recompiles"]),
            "frontier_bytes": float(d["frontier_bytes"]),
            "truncated": int(d["truncated"]),
            "rung_hits": {int(kv.split(":")[0]): int(kv.split(":")[1])
                          for kv in d["rung_hits"].split(";") if kv},
            "throughput_tps": float(d["throughput_tps"]),
        }
    return out


def parse_resilience(rows) -> dict:
    """``resilience/<model>/<ds>/<scenario>`` rows -> {case: record}.

    ``step_us`` is the latency wall (recorded, never gated); every other
    field is a deterministic counter from a seeded fault schedule — the
    gate compares them EXACTLY (same seed + same queue must replay the same
    recovery trajectory)."""
    out: dict = {}
    for name, us, derived in rows or []:
        m = re.fullmatch(r"resilience/(\w+)/(\w+)/(\w+)", name)
        if not m:
            continue
        d = dict(kv.split("=", 1) for kv in derived.split())
        rec: dict = {"step_us": round(us, 1)}
        for k, v in d.items():
            rec[k] = int(v)
        out[f"{m.group(1)}/{m.group(2)}/{m.group(3)}"] = rec
    return out


def parse_residency(rows) -> dict:
    """``residency/<model>/<ds>/c<C>`` rows -> {case: record}.

    ``na_us`` is the latency wall (recorded, never gated); the counters are
    deterministic host-side degree-ordering output — hits/misses/rows replay
    exactly, so ``--check`` compares them at exact equality."""
    out: dict = {}
    for name, us, derived in rows or []:
        m = re.fullmatch(r"residency/(\w+)/(\w+)/c(\d+)", name)
        if not m:
            continue
        d = dict(kv.split("=", 1) for kv in derived.split())
        out[f"{m.group(1)}/{m.group(2)}/c{m.group(3)}"] = {
            "na_us": round(us, 1),
            "cache_rows": int(d["cache_rows"]),
            "hits": int(d["hits"]),
            "misses": int(d["misses"]),
            "rows": int(d["rows"]),
            "hit_rate": float(d["hit_rate"]),
            "na_hbm_bytes": float(d["na_hbm_bytes"]),
            "bytes_saved": float(d["bytes_saved"]),
        }
    return out


def check_regression(results: dict, threshold: float = 0.20) -> None:
    """Bench-regression gate: diff the fresh NA/SA stage costs against the
    committed ``BENCH_hgnn.json``; fail on >``threshold`` regression.

    Two comparisons per case/stage: wall time (gated behind an absolute
    floor — CPU CI timers are noisy and the committed numbers come from a
    different machine) and the characterization records (FLOPs / HBM bytes
    from the compiled HLO — deterministic, so no floor: a >20% byte or FLOP
    growth is a real code regression regardless of the runner).

    The comparison covers EVERY stage the committed snapshot records for a
    case the fresh run reproduced — including the partitioned flow's
    halo-exchange stage — and a committed stage that is *missing* from the
    fresh run fails loudly instead of silently passing (a disappeared stage
    usually means the breakdown regexes and the executor drifted apart).
    The ``partition`` section gates the same way: halo traffic is
    deterministic partitioner output, so byte/cut drift needs no floor."""
    sb = results.get("bench_stage_breakdown")
    pt = results.get("bench_partition")
    ly = results.get("bench_layers")
    sv = results.get("bench_serving")
    rz = results.get("bench_resilience")
    rd = results.get("bench_residency")
    if (not sb and not pt and not ly and not sv and not rz
            and not rd) or not BENCH_JSON.exists():
        return
    try:
        committed = json.loads(BENCH_JSON.read_text())
    except json.JSONDecodeError:
        return
    old = committed.get("stage_breakdown_us", {})
    old_char = committed.get("stage_characterization", {})
    floor_us = float(os.environ.get("BENCH_GATE_FLOOR_US", "2000"))
    regressions = []

    def gate_wall(label, prev, new):
        if prev and new and new > prev * (1 + threshold) \
                and new - prev > floor_us:
            regressions.append(f"{label}: {prev:.0f} -> {new:.0f} us "
                               f"(+{100 * (new / prev - 1):.0f}%)")

    if sb:
        fresh = parse_breakdown(sb)
        if not fresh and old:
            # the module produced rows but the parser recognized none: the
            # row naming and the gate drifted apart — exactly the silent
            # pass this gate exists to prevent
            regressions.append("bench_stage_breakdown rows parsed to zero "
                               "cases (row naming / gate regex drift?)")
        for case, stages in fresh.items():
            for stage in sorted(set(old.get(case, {})) | set(stages)):
                prev, new = old.get(case, {}).get(stage), stages.get(stage)
                if prev and new is None:
                    regressions.append(
                        f"{case}/{stage}: recorded stage missing from the "
                        "fresh run")
                    continue
                gate_wall(f"{case}/{stage}", prev, new)
        for case, stages in parse_characterization(sb).items():
            for stage in sorted(set(old_char.get(case, {})) | set(stages)):
                prev = old_char.get(case, {}).get(stage)
                new = stages.get(stage)
                if prev and new is None:
                    regressions.append(
                        f"{case}/{stage}: recorded characterization missing "
                        "from the fresh run")
                    continue
                if not prev or not new:
                    continue
                for metric in ("flops", "hbm_bytes"):
                    if new[metric] > prev[metric] * (1 + threshold):
                        regressions.append(
                            f"{case}/{stage} {metric}: {prev[metric]:.3g} -> "
                            f"{new[metric]:.3g} "
                            f"(+{100 * (new[metric] / prev[metric] - 1):.0f}%)")
    if pt:
        # Wall times in this section sit at the tens-of-ms scale where
        # shared-runner noise swings 3x, so they are recorded (for the
        # handbook) but not gated — the gate covers stage PRESENCE and the
        # partitioner's deterministic outputs (halo bytes / cut edges are
        # exact re-runs of the same host algorithm on the same graph).
        old_part = committed.get("partition", {})
        fresh_part = parse_partition(pt)
        if not fresh_part and old_part:
            regressions.append("bench_partition rows parsed to zero cases "
                               "(row naming / gate regex drift?)")
        for case, rec in fresh_part.items():
            prev = old_part.get(case)
            if not prev:
                continue
            for stage_key in ("NA_us", "gather_halo_us"):
                if stage_key in prev and stage_key not in rec:
                    regressions.append(f"partition/{case}/{stage_key}: "
                                       "recorded stage missing from the "
                                       "fresh run")
            for metric in ("halo_bytes", "cut_edges"):
                pv, nv = prev.get(metric), rec.get(metric)
                if pv and nv is not None and nv > pv * (1 + threshold):
                    regressions.append(
                        f"partition/{case} {metric}: {pv:.3g} -> {nv:.3g} "
                        f"(+{100 * (nv / pv - 1):.0f}%)")
    if ly:
        # depth sweep: wall times stay ungated (tens-of-ms CPU noise); the
        # gate covers per-layer stage PRESENCE, the deterministic per-layer
        # characterization records, and the halo-bytes x L traffic (exact
        # re-runs of the same partitioner + HLO walk)
        old_layers = committed.get("layers", {})
        fresh_layers = parse_layers(ly)
        if not fresh_layers and old_layers:
            regressions.append("bench_layers rows parsed to zero cases "
                               "(row naming / gate regex drift?)")
        for case, rec in fresh_layers.items():
            prev = old_layers.get(case)
            if not prev:
                continue
            for st in prev.get("stages_us", {}):
                if st not in rec.get("stages_us", {}):
                    regressions.append(f"layers/{case}/{st}: recorded stage "
                                       "missing from the fresh run")
            for st, pm in prev.get("char", {}).items():
                nm = rec.get("char", {}).get(st)
                if nm is None:
                    regressions.append(f"layers/{case}/char/{st}: recorded "
                                       "characterization missing from the "
                                       "fresh run")
                    continue
                for metric in ("flops", "hbm_bytes"):
                    if not pm[metric]:
                        # zero baseline (e.g. RGCN's identity hidden FP has
                        # zero FLOPs): any appearance of work is a change
                        # worth flagging, and there is no percent to compute
                        if nm[metric]:
                            regressions.append(
                                f"layers/{case}/{st} {metric}: 0 -> "
                                f"{nm[metric]:.3g}")
                        continue
                    if nm[metric] > pm[metric] * (1 + threshold):
                        regressions.append(
                            f"layers/{case}/{st} {metric}: {pm[metric]:.3g} "
                            f"-> {nm[metric]:.3g} "
                            f"(+{100 * (nm[metric] / pm[metric] - 1):.0f}%)")
            if prev.get("halo") and not rec.get("halo"):
                regressions.append(f"layers/{case}/halo: recorded halo "
                                   "record missing from the fresh run")
            for metric in ("halo_bytes", "halo_bytes_total"):
                pv = prev.get("halo", {}).get(metric)
                nv = rec.get("halo", {}).get(metric)
                if pv and nv is not None and nv > pv * (1 + threshold):
                    regressions.append(
                        f"layers/{case} {metric}: {pv:.3g} -> {nv:.3g} "
                        f"(+{100 * (nv / pv - 1):.0f}%)")
    if sv:
        # serving gate: wall latencies are recorded but NEVER gated (the
        # partition-section convention); the gate covers the deterministic
        # quantities only — sampled frontier bytes and bucket-ladder hit
        # counts are exact re-runs of the same host sampler on the same
        # graph and queue, and the post-warmup recompile count must stay 0
        old_serving = committed.get("serving", {})
        fresh_serving = parse_serving(sv)
        if not fresh_serving and old_serving:
            regressions.append("bench_serving rows parsed to zero cases "
                               "(row naming / gate regex drift?)")
        for case, rec in fresh_serving.items():
            prev = old_serving.get(case)
            if not prev:
                continue
            if rec["recompiles"] > prev.get("recompiles", 0):
                regressions.append(
                    f"serving/{case} recompiles: {prev.get('recompiles', 0)} "
                    f"-> {rec['recompiles']} (post-warmup compilation — a "
                    "batch shape escaped the ladder)")
            pv = prev.get("frontier_bytes")
            if pv and rec["frontier_bytes"] > pv * (1 + threshold):
                regressions.append(
                    f"serving/{case} frontier_bytes: {pv:.3g} -> "
                    f"{rec['frontier_bytes']:.3g} "
                    f"(+{100 * (rec['frontier_bytes'] / pv - 1):.0f}%)")
            old_hits = {int(k): v
                        for k, v in prev.get("rung_hits", {}).items()}
            for rung, n_prev in old_hits.items():
                n_new = rec["rung_hits"].get(rung, 0)
                if n_prev and n_new > n_prev * (1 + threshold):
                    regressions.append(
                        f"serving/{case} rung_hits[{rung}]: {n_prev} -> "
                        f"{n_new} (ladder dispatch drift)")
    if rz:
        # resilience gate: counters replay a seeded fault schedule over a
        # fixed queue, so the comparison is EXACT equality — any drift in
        # retries / failed requests / shed / degrade levels / failover
        # outcome is a recovery-path behavior change, not noise.  Walls
        # (step_us) stay ungated as everywhere else.
        old_rz = committed.get("resilience", {})
        fresh_rz = parse_resilience(rz)
        if not fresh_rz and old_rz:
            regressions.append("bench_resilience rows parsed to zero cases "
                               "(row naming / gate regex drift?)")
        for case, rec in fresh_rz.items():
            prev = old_rz.get(case)
            if not prev:
                continue
            for key in sorted(set(prev) - {"step_us"}):
                if key not in rec:
                    regressions.append(
                        f"resilience/{case} {key}: recorded counter missing "
                        "from the fresh run")
                elif rec[key] != prev[key]:
                    regressions.append(
                        f"resilience/{case} {key}: {prev[key]} -> {rec[key]} "
                        "(seeded chaos counters must replay exactly)")
    if rd:
        # residency gate: the hit/miss counters are deterministic output of
        # the degree ordering over the same graph's gather tables, so the
        # comparison is EXACT equality — any drift means the hot-set
        # selection or the reference counting changed, not noise.  The NA
        # bytes after the cache accounting are deterministic too (HLO walk
        # minus counters) and gate at the usual growth threshold; walls
        # (na_us) stay ungated as everywhere else.
        old_rd = committed.get("residency", {})
        fresh_rd = parse_residency(rd)
        if not fresh_rd and old_rd:
            regressions.append("bench_residency rows parsed to zero cases "
                               "(row naming / gate regex drift?)")
        for case, rec in fresh_rd.items():
            prev = old_rd.get(case)
            if not prev:
                continue
            for key in ("cache_rows", "hits", "misses", "rows"):
                if key not in rec:
                    regressions.append(
                        f"residency/{case} {key}: recorded counter missing "
                        "from the fresh run")
                elif rec[key] != prev.get(key):
                    regressions.append(
                        f"residency/{case} {key}: {prev.get(key)} -> "
                        f"{rec[key]} (degree-ordered counters must replay "
                        "exactly)")
            pv = prev.get("na_hbm_bytes")
            if pv and rec["na_hbm_bytes"] > pv * (1 + threshold):
                regressions.append(
                    f"residency/{case} na_hbm_bytes: {pv:.3g} -> "
                    f"{rec['na_hbm_bytes']:.3g} "
                    f"(+{100 * (rec['na_hbm_bytes'] / pv - 1):.0f}%)")
    if regressions:
        raise SystemExit("bench regression gate (>"
                         f"{int(threshold * 100)}% vs {BENCH_JSON.name}): "
                         + "; ".join(regressions))
    print(f"# bench regression gate OK (vs {BENCH_JSON.name})", flush=True)


def write_bench_json(results: dict) -> None:
    """Fold HGNN trajectory rows into BENCH_hgnn.json.

    Merges into the existing file so running one module never clobbers the
    other module's committed section; only called when every selected
    module succeeded."""
    data: dict = {"schema": 1, "source": "benchmarks/run.py"}
    if BENCH_JSON.exists():
        try:
            data.update(json.loads(BENCH_JSON.read_text()))
        except json.JSONDecodeError:
            pass  # rewrite a corrupt baseline from scratch
    sb = results.get("bench_stage_breakdown")
    if sb:
        for name, us, derived in sb:
            if name == "fig2/avg_NA_share":
                m2 = re.search(r"avg_na_share=([\d.]+)", derived)
                if m2:
                    data["avg_na_share_pct"] = float(m2.group(1))
        # merge per case: a BENCH_SMOKE run (one case) must not shrink the
        # committed multi-case baseline
        data.setdefault("stage_breakdown_us", {}).update(parse_breakdown(sb))
        data.setdefault("stage_characterization", {}).update(
            parse_characterization(sb))
    nf = results.get("bench_na_fused")
    if nf:
        fused: dict = {}
        for name, us, derived in nf:
            if name == "na_fused/csr_baseline":
                fused["baseline_csr_us"] = round(us, 1)
            elif name == "na_fused/padded_per_head":
                fused["per_head_us"] = round(us, 1)
                m = re.search(r"na_launches=(\d+)", derived)
                fused["na_launches_per_head"] = int(m.group(1)) if m else None
            elif name == "na_fused/fused_all_heads":
                fused["fused_us"] = round(us, 1)
                m = re.search(r"speedup_vs_csr=([\d.]+)x", derived)
                fused["speedup_vs_baseline"] = float(m.group(1)) if m else None
                fused["na_launches_fused"] = 1
            elif name == "na_fused/bucketed_xla":
                fused["bucketed_us"] = round(us, 1)
                m = re.search(r"speedup_vs_csr=([\d.]+)x", derived)
                fused["bucketed_speedup_vs_csr"] = (float(m.group(1))
                                                    if m else None)
            elif name == "na_fused/kernel_interpret_parity":
                m = re.search(r"max_abs_err=([\d.e+-]+)", derived)
                fused["kernel_max_abs_err"] = float(m.group(1)) if m else None
        data["na_fused"] = fused
    se = results.get("bench_sa_epilogue")
    if se:
        epi: dict = {}
        for name, us, derived in se:
            d = dict(kv.split("=", 1) for kv in derived.split())
            if name == "sa_epilogue/two_pass":
                epi["two_pass_us"] = round(us, 1)
                epi["two_pass_hbm_bytes"] = float(d["hbm_bytes"])
                epi["z_bytes"] = float(d["z_bytes"])
            elif name == "sa_epilogue/fused":
                epi["fused_us"] = round(us, 1)
                epi["fused_hbm_bytes"] = float(d["hbm_bytes"])
                epi["z_passes_saved"] = float(d["z_passes_saved"])
            elif name == "sa_epilogue/kernel_interpret_parity":
                epi["kernel_max_abs_err"] = float(d["max_abs_err"])
        data["sa_epilogue"] = epi
    pt = results.get("bench_partition")
    if pt:
        # merge per case so a BENCH_SMOKE run (one model, two Ks) never
        # shrinks the committed multi-case sweep
        data.setdefault("partition", {}).update(parse_partition(pt))
    ly = results.get("bench_layers")
    if ly:
        # merge per case so a BENCH_SMOKE run (one model, two depths) never
        # shrinks the committed depth sweep
        data.setdefault("layers", {}).update(parse_layers(ly))
    sv = results.get("bench_serving")
    if sv:
        # merge per case so a BENCH_SMOKE run (one case, one slot plan)
        # never shrinks the committed serving sweep
        data.setdefault("serving", {}).update(parse_serving(sv))
    rz = results.get("bench_resilience")
    if rz:
        # merge per case so a BENCH_SMOKE run (one chaos case + failover)
        # never shrinks the committed chaos sweep
        data.setdefault("resilience", {}).update(parse_resilience(rz))
    rd = results.get("bench_residency")
    if rd:
        # merge per case so a BENCH_SMOKE run (one case, two capacities)
        # never shrinks the committed capacity sweep
        data.setdefault("residency", {}).update(parse_residency(rd))
    if sb or nf or se or pt or ly or sv or rz or rd:
        BENCH_JSON.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
        print(f"# wrote {BENCH_JSON.name}", flush=True)


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:]
    check = "--check" in argv
    only = [a for a in argv if a != "--check"] or None
    print("name,us_per_call,derived")
    failures = 0
    results: dict = {}
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            from benchmarks.common import emit

            rows = mod.run()
            emit(rows)
            results[name] = rows
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED\n{traceback.format_exc()}", flush=True)
    if not failures:  # never record a partial/failed run as the baseline
        if check:  # gate against the committed snapshot BEFORE overwriting
            check_regression(results)
        write_bench_json(results)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == "__main__":
    main()
