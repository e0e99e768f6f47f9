"""Workload characterization — the paper's contribution as a framework feature.

The paper classifies CUDA kernels into four classes and attributes time/
bandwidth/AI to each (Fig. 3/4, Table 3).  On TPU there are no CUDA kernels;
the equivalent artifact is the compiled per-device HLO module.  This module
walks it with a call-graph-aware cost model:

  * kernel classes:  DM (dot/conv), TB (gather/scatter — graph topology,
    MoE routing, embedding lookups), EW (elementwise/reduce), DR (pure data
    rearrangement: copy/transpose/concat/slice/DUS), COLL (collectives),
    OTHER (custom calls, rng, sort).
  * fusions: FLOPs from the fused computation interior; HBM bytes counted at
    the fusion BOUNDARY (operands+result) — exactly the memory a fused TPU
    kernel moves.
  * while loops (lax.scan over layers / kv chunks): body cost multiplied by
    the ``known_trip_count`` XLA records in backend_config — this is what
    ``compiled.cost_analysis()`` gets wrong (it counts loop bodies once).

Outputs the three roofline terms against the device's published peaks
(``PEAKS``, keyed by ``device_kind``; TPU v5e shown):
    compute    = FLOPs / (chips x 197 TFLOP/s)
    memory     = HBM bytes / (chips x 819 GB/s)
    collective = collective bytes / (chips x 50 GB/s/link)
(all quantities here are per-device, i.e. already divided by chips).
"""
from __future__ import annotations

import json
import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# ---- Per-chip peaks, keyed by JAX ``device_kind`` ----
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
# 819 GB/s HBM per chip.  link_bw is a conservative one-ICI-link 50 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
# HLO compiled on a host without a TPU is costed as if it ran on this chip.
CPU_MODEL_KIND = "TPU v5 lite"


def peaks(device_kind: Optional[str] = None) -> Dict:
    """Peaks of ``device_kind`` (default: JAX's first device), with the kind
    they belong to under ``"device_kind"``.  A kind missing from ``PEAKS``
    raises; a non-TPU default device gets the ``CPU_MODEL_KIND`` model."""
    if device_kind is None:
        import jax

        dev = jax.devices()[0]
        device_kind = (dev.device_kind if dev.platform == "tpu"
                       else CPU_MODEL_KIND)
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add it to PEAKS with its source")
    return {"device_kind": device_kind, **PEAKS[device_kind]}

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)
DM_OPS = ("dot", "convolution")
TB_OPS = ("gather", "scatter", "dynamic-slice")
DR_OPS = ("copy", "transpose", "reshape", "concatenate", "slice", "pad",
          "dynamic-update-slice", "reverse", "broadcast")
ZERO_OPS = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "after-all", "iota", "partition-id", "replica-id", "domain",
            "opt-barrier")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(.*?\)|[\w\[\]\{\},\. ]+?)\s+"
    r"([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\(.*\))?\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[\\"\{:n ]+(\d+)')
_CALLS_RE = re.compile(r"calls=%?([\w\.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TO_APPLY_RE = re.compile(r"to_apply=%?([\w\.\-]+)")


def shape_bytes(type_str: str) -> int:
    """Bytes of an HLO type string (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def shape_elems(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n
    return total


@dataclass
class Instr:
    name: str
    type_str: str
    opcode: str
    rest: str  # everything after the opening paren (operands + attrs)
    result_bytes: int = 0
    result_elems: int = 0

    def operands(self) -> List[str]:
        # operand list terminates at the first unmatched ')'
        depth = 1
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return re.findall(r"%([\w\.\-]+)", self.rest[:i])
        return re.findall(r"%([\w\.\-]+)", self.rest)

    def attrs(self) -> str:
        depth = 1
        for i, ch in enumerate(self.rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return self.rest[i + 1:]
        return ""


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    symtab: Dict[str, Instr] = field(default_factory=dict)


def parse_hlo(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    entry: Optional[str] = None
    cur: Optional[Computation] = None
    for line in text.splitlines():
        m = _COMP_RE.match(line)
        if m and ("->" in line) and line.rstrip().endswith("{"):
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                entry = cur.name
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        mi = _INSTR_RE.match(line)
        if mi:
            ins = Instr(mi.group(1), mi.group(2).strip(), mi.group(3),
                        mi.group(4))
            ins.result_bytes = shape_bytes(ins.type_str)
            ins.result_elems = shape_elems(ins.type_str)
            cur.instrs.append(ins)
            cur.symtab[ins.name] = ins
    return comps, entry


def classify(opcode: str) -> str:
    if opcode in ZERO_OPS:
        return "ZERO"
    if any(opcode.startswith(c) for c in COLLECTIVES):
        return "COLL"
    if opcode in DM_OPS or opcode.startswith("dot"):
        return "DM"
    if opcode in TB_OPS:
        return "TB"
    if opcode in DR_OPS:
        return "DR"
    if opcode in ("fusion", "while", "call", "conditional", "custom-call",
                  "sort", "rng", "rng-bit-generator"):
        return opcode.upper()
    return "EW"  # default: elementwise / reduce / compare / convert ...


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dot_flops(ins: Instr, comp: Computation) -> float:
    """2 x prod(result) x prod(lhs contracting dims)."""
    ops = ins.operands()
    k = 1
    m = _CONTRACT_RE.search(ins.attrs())
    if m and ops:
        lhs = comp.symtab.get(ops[0])
        if lhs is not None:
            dims_m = _SHAPE_RE.findall(lhs.type_str)
            if dims_m:
                dims = [int(d) for d in dims_m[0][1].split(",") if d]
                for ci in m.group(1).split(","):
                    if ci != "" and int(ci) < len(dims):
                        k *= dims[int(ci)]
    return 2.0 * ins.result_elems * k


class CostWalker:
    """Accumulates per-class flops / hbm bytes / collective bytes across the
    call graph, multiplying while bodies by known_trip_count."""

    def __init__(self, comps: Dict[str, Computation]):
        self.comps = comps
        self._memo: Dict[Tuple[str, bool], Dict] = {}

    def _zero(self) -> Dict:
        return {"flops": defaultdict(float), "hbm": defaultdict(float),
                "coll": 0.0, "coll_ops": defaultdict(float),
                "count": defaultdict(int)}

    def _merge(self, a: Dict, b: Dict, mult: float = 1.0):
        for k, v in b["flops"].items():
            a["flops"][k] += v * mult
        for k, v in b["hbm"].items():
            a["hbm"][k] += v * mult
        a["coll"] += b["coll"] * mult
        for k, v in b.get("coll_ops", {}).items():
            a["coll_ops"][k] += v * mult
        for k, v in b["count"].items():
            a["count"][k] += v * int(mult)

    def _called(self, ins: Instr) -> List[str]:
        """Computations executed by this op (while -> body only; the
        condition is O(1) bookkeeping)."""
        attrs = ins.attrs()
        out = []
        for rex in (_CALLS_RE, _BODY_RE, _TO_APPLY_RE):
            m = rex.search(attrs)
            if m and m.group(1) in self.comps:
                out.append(m.group(1))
        m = _BRANCH_RE.search(attrs)
        if m:
            for name in m.group(1).split(","):
                name = name.strip().lstrip("%")
                if name in self.comps:
                    out.append(name)
        return out

    def interior_flops(self, cname: str) -> Dict:
        """FLOPs (by class) of a fused computation's interior (no bytes)."""
        key = (cname, True)
        if key in self._memo:
            return self._memo[key]
        acc = self._zero()
        comp = self.comps[cname]
        for ins in comp.instrs:
            cls = classify(ins.opcode)
            if cls == "ZERO":
                continue
            if cls == "DM":
                acc["flops"]["DM"] += _dot_flops(ins, comp)
            elif cls in ("EW",):
                acc["flops"]["EW"] += ins.result_elems
            elif cls == "TB":
                acc["flops"]["TB"] += ins.result_elems
            elif cls in ("FUSION", "CALL", "WHILE", "CONDITIONAL"):
                for sub in self._called(ins):
                    self._merge(acc, self.interior_flops(sub))
            acc["count"][cls if cls in ("DM", "TB", "EW", "DR") else "OTHER"] += 1
        self._memo[key] = acc
        return acc

    def fusion_class(self, cname: str) -> str:
        f = self.interior_flops(cname)
        if f["flops"]["DM"] > 0:
            return "DM"
        if f["flops"]["TB"] > 0 or f["count"]["TB"] > 0:
            return "TB"
        if f["flops"]["EW"] > 0:
            return "EW"
        return "DR"

    def walk(self, cname: str) -> Dict:
        """Full cost of a computation executed once (top-level semantics)."""
        key = (cname, False)
        if key in self._memo:
            return self._memo[key]
        acc = self._zero()
        comp = self.comps[cname]
        for ins in comp.instrs:
            cls = classify(ins.opcode)
            if cls == "ZERO":
                continue
            if cls == "COLL":
                if ins.opcode.endswith("-done"):
                    continue
                acc["coll"] += ins.result_bytes
                base = ins.opcode.replace("-start", "")
                acc["coll_ops"][base] += ins.result_bytes
                acc["count"]["COLL"] += 1
                continue
            if cls == "FUSION":
                fclass = "EW"
                for sub in self._called(ins):
                    fint = self.interior_flops(sub)
                    self._merge(acc, {"flops": fint["flops"],
                                      "hbm": {}, "coll": 0.0, "count": {}})
                    fclass = self.fusion_class(sub)
                op_bytes = sum(
                    comp.symtab[o].result_bytes for o in ins.operands()
                    if o in comp.symtab)
                acc["hbm"][fclass] += op_bytes + ins.result_bytes
                acc["count"][fclass] += 1
                continue
            if cls == "WHILE":
                trip = 1
                m = _TRIP_RE.search(ins.attrs())
                if m:
                    trip = int(m.group(1))
                for sub in self._called(ins):
                    self._merge(acc, self.walk(sub), mult=trip)
                continue
            if cls in ("CALL", "CONDITIONAL"):
                for sub in self._called(ins):
                    self._merge(acc, self.walk(sub))
                continue
            # plain (unfused) op at top level
            op_bytes = sum(comp.symtab[o].result_bytes for o in ins.operands()
                           if o in comp.symtab)
            bytes_moved = op_bytes + ins.result_bytes
            if cls == "DM":
                acc["flops"]["DM"] += _dot_flops(ins, comp)
                acc["hbm"]["DM"] += bytes_moved
            elif cls == "TB":
                acc["flops"]["TB"] += ins.result_elems
                acc["hbm"]["TB"] += bytes_moved
            elif cls == "DR":
                acc["hbm"]["DR"] += bytes_moved
            elif cls in ("CUSTOM-CALL", "SORT", "RNG", "RNG-BIT-GENERATOR"):
                acc["hbm"]["OTHER"] += bytes_moved
            else:
                acc["flops"]["EW"] += ins.result_elems
                acc["hbm"]["EW"] += bytes_moved
            acc["count"][cls if cls in ("DM", "TB", "EW", "DR") else "OTHER"] += 1
        self._memo[key] = acc
        return acc


def analyze_hlo_text(text: str) -> Dict:
    comps, entry = parse_hlo(text)
    if entry is None:
        raise ValueError("no ENTRY computation found")
    walker = CostWalker(comps)
    acc = walker.walk(entry)
    flops = dict(acc["flops"])
    hbm = dict(acc["hbm"])
    return {
        "flops_by_class": {k: float(v) for k, v in flops.items()},
        "hbm_bytes_by_class": {k: float(v) for k, v in hbm.items()},
        "collective_bytes": float(acc["coll"]),
        "collective_bytes_by_op": {k: float(v) for k, v in acc["coll_ops"].items()},
        "op_counts": dict(acc["count"]),
        "total_flops": float(sum(flops.values())),
        "total_hbm_bytes": float(sum(hbm.values())),
    }


# ---------------------------------------------------------------------------
# partitioned-execution traffic record
# ---------------------------------------------------------------------------


def partition_traffic(part: Dict, h_own: Dict, layers: int = 1) -> Dict:
    """Halo-exchange / edge-cut record for the partitioned execution mode.

    ``part`` is the device batch's partition table (``repro.dist.partition``:
    ``halo_mask`` per type + host-side ``meta`` counters); ``h_own`` the
    per-type ``[K, n, ...]`` feature shards entering the ``gather_halo``
    stage, whose trailing dims price a halo row in bytes.  This is the
    paper-facing view of the new communication stage — the bytes that cross
    partitions because an edge was cut — independent of how the exchange is
    lowered (shard_map all-gather vs GSPMD resharding).

    ``layers``: an L-layer stack re-runs the exchange once per layer on the
    *updated* features (the halo maps are graph-invariant and every layer's
    tables are hidden-width), so the total exchanged traffic is the
    per-exchange volume × L — reported as ``halo_bytes_total`` /
    ``halo_rows_total`` next to the per-exchange figures.
    """
    import numpy as np

    halo_rows = 0.0
    halo_bytes = 0.0
    for t, m in part["halo_mask"].items():
        rows = float(np.asarray(m).sum())
        h = h_own[t]
        row_bytes = 1.0
        for d in h.shape[2:]:
            row_bytes *= d
        row_bytes *= h.dtype.itemsize
        halo_rows += rows
        halo_bytes += rows * row_bytes
    meta = part["meta"]
    cut = int(meta["cut_edges"])
    total = int(meta["edges_total"])
    return {
        "k": int(meta["k"]),
        "halo_rows": halo_rows,
        "halo_bytes": halo_bytes,
        "cut_edges": cut,
        "edges_total": total,
        "cut_ratio": cut / max(total, 1),
        "layers": int(layers),
        "halo_rows_total": halo_rows * layers,
        "halo_bytes_total": halo_bytes * layers,
    }


def residency_record(counters: Dict, row_bytes: int, layers: int = 1) -> Dict:
    """Hot-feature residency record (``repro.core.residency``).

    ``counters`` are the deterministic hit/miss counters attached to a
    prepared batch (single-device: hot references in the remapped NA index
    tables; partitioned: hot entries in the halo tables) — replayable
    exactly from (graph, seed, plan), which is what the residency bench
    gates at exact equality.  ``row_bytes`` prices one gathered feature row
    (the hidden width — NA gathers projected tables); ``layers`` is the
    number of cached stages in the L-layer stack.  The hot set is
    layer-invariant, so every layer saves ``hits × row_bytes`` of HBM
    gather traffic while the cache fill (``cache_rows × row_bytes``) is
    paid once — HiHGNN-style inter-layer reuse.
    """
    hits = int(counters["hits"])
    misses = int(counters["misses"])
    rows = int(counters["rows"])
    cache_rows = int(counters["cache_rows"])
    fill = cache_rows * int(row_bytes)
    per_layer = hits * int(row_bytes)
    return {
        "cache_rows": cache_rows,
        "hits": hits,
        "misses": misses,
        "rows": rows,
        "hit_rate": hits / max(rows, 1),
        "row_bytes": int(row_bytes),
        "layers": int(layers),
        "fill_bytes": fill,
        "bytes_saved_per_layer": per_layer,
        "bytes_saved_total": per_layer * int(layers) - fill,
    }


def resilience_record(stats: Dict) -> Dict:
    """Resilience counters record for request-path serving.

    ``stats`` is :meth:`repro.serve.engine.HGNNServeEngine.stats`'s return
    value.  Normalizes the nested resilience counters into the flat
    deterministic record the chaos bench and the characterization handbook
    report: per-status request counts, retry/failure totals, the
    degradation trajectory (transitions + peak level — both strictly inside
    the warmed ladder, so ``recompiles`` belongs in the same record), and
    the partition-failover outcome.  Every field replays a seeded fault
    schedule exactly; none is timing-dependent.
    """
    rs = stats.get("resilience", {})
    return {
        "ok_requests": int(rs.get("ok_requests", 0)),
        "partial_requests": int(rs.get("partial_requests", 0)),
        "failed_requests": int(rs.get("failed_requests", 0)),
        "rejected": int(rs.get("rejected", 0)),
        "shed": int(rs.get("shed", 0)),
        "deduped_rows": int(rs.get("deduped_rows", 0)),
        "retries": int(rs.get("retries", 0)),
        "failed_steps": int(rs.get("failed_steps", 0)),
        "deadline_expired": int(rs.get("deadline_expired", 0)),
        "degrade_transitions": int(rs.get("degrade_transitions", 0)),
        "recover_transitions": int(rs.get("recover_transitions", 0)),
        "max_degrade_level": int(rs.get("max_degrade_level", 0)),
        "partition_failovers": int(rs.get("partition_failovers", 0)),
        "lost_partitions": list(rs.get("lost_partitions", [])),
        "steps": int(stats.get("steps", 0)),
        "recompiles": stats.get("compiles_after_warmup"),
    }


# ---------------------------------------------------------------------------
# async stage-graph overlap accounting
# ---------------------------------------------------------------------------


def overlap_accounting(edges: Dict[str, Tuple[str, ...]],
                       walls_us: Dict[str, float]) -> Dict:
    """Critical-path accounting over the plan-derived stage DAG.

    ``edges`` is :meth:`repro.core.pipeline.StageGraphExecutor.
    schedule_edges` (stage → its dependencies, topological order);
    ``walls_us`` the measured per-stage walls.  The *serial sum* is the
    blocking schedule's lower bound (every stage waits for the previous
    one); the *critical path* is the overlapped schedule's — the longest
    dependency chain when independent stages run concurrently.  Their gap
    is the overlap saving; per-stage **exposure** is how much of the
    critical path a stage is actually responsible for (critical path minus
    the critical path with that stage's wall zeroed) — a fully-hidden
    stage (e.g. a halo exchange shorter than the owned-rows NA it overlaps)
    exposes ~0 even with a large wall.
    """
    finish: Dict[str, float] = {}
    for n in edges:  # topological by construction
        finish[n] = (max((finish[d] for d in edges[n]), default=0.0)
                     + walls_us.get(n, 0.0))
    crit = max(finish.values(), default=0.0)

    def _crit_without(skip: str) -> float:
        f: Dict[str, float] = {}
        for n in edges:
            w = 0.0 if n == skip else walls_us.get(n, 0.0)
            f[n] = max((f[d] for d in edges[n]), default=0.0) + w
        return max(f.values(), default=0.0)

    serial = float(sum(walls_us.get(n, 0.0) for n in edges))
    return {
        "serial_sum_us": serial,
        "critical_path_us": float(crit),
        "overlap_saved_us": float(serial - crit),
        "exposure_us": {n: float(crit - _crit_without(n)) for n in edges},
    }


# ---------------------------------------------------------------------------
# model-level analytics + roofline
# ---------------------------------------------------------------------------


def analytic_param_counts(cfg) -> Tuple[float, float]:
    """(total params, active params) from the config (no instantiation)."""
    import jax

    if cfg.family == "encdec":
        from repro.nn.encdec import init_encdec_params

        tree = jax.eval_shape(lambda: init_encdec_params(jax.random.key(0), cfg))
    else:
        from repro.nn.transformer import init_lm_params

        tree = jax.eval_shape(lambda: init_lm_params(jax.random.key(0), cfg))
    total = 0.0
    expert = 0.0

    def visit(path, leaf):
        nonlocal total, expert
        n = float(math.prod(leaf.shape))
        total += n
        names = [str(p.key) for p in path
                 if isinstance(p, __import__("jax").tree_util.DictKey)]
        if "moe" in names and names[-1] in ("w_gate", "w_up", "w_down"):
            expert += n

    import jax.tree_util as jtu

    jtu.tree_map_with_path(visit, tree)
    active = total - expert
    if cfg.moe is not None and expert > 0:
        active += expert * cfg.moe.top_k / cfg.moe.n_experts
    return total, active


def model_flops(cfg, shape, n_total: float, n_active: float) -> float:
    """The brief's MODEL_FLOPS: 6·N·D train (N_active for MoE), 2·N·D fwd."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: 1 token / sequence


def roofline(per_device: Dict, n_chips: int, model_fl: float,
             device_kind: Optional[str] = None) -> Dict:
    pk = peaks(device_kind)
    t_c = per_device["total_flops"] / pk["flops"]
    t_m = per_device["total_hbm_bytes"] / pk["hbm_bw"]
    t_l = per_device["collective_bytes"] / pk["link_bw"]
    bound = max((t_c, "compute"), (t_m, "memory"), (t_l, "collective"))[1]
    t_step = max(t_c, t_m, t_l)
    model_fl_dev = model_fl / n_chips
    return {
        "peaks_of": pk["device_kind"],
        "compute_s": t_c,
        "memory_s": t_m,
        "collective_s": t_l,
        "bound": bound,
        "step_time_s": t_step,
        "model_flops_total": model_fl,
        "model_flops_per_device": model_fl_dev,
        "useful_flops_ratio": model_fl_dev / per_device["total_flops"]
        if per_device["total_flops"] else 0.0,
        "mfu_proxy": model_fl_dev / (t_step * pk["flops"]) if t_step else 0.0,
        "roofline_fraction": ((model_fl_dev / pk["flops"]) / t_step
                              if t_step else 0.0),
    }


def analyze_compiled(compiled, cfg=None, shape=None, n_chips: int = 1) -> Dict:
    """Full report for a compiled (post-SPMD, per-device) executable."""
    rep = analyze_hlo_text(compiled.as_text())
    out = {"hlo": rep}
    if cfg is not None and shape is not None:
        n_total, n_active = analytic_param_counts(cfg)
        mf = model_flops(cfg, shape, n_total, n_active)
        out["params_total"] = n_total
        out["params_active"] = n_active
        out["roofline"] = roofline(rep, n_chips, mf)
    else:
        out["roofline"] = roofline(rep, n_chips, 0.0)
    return out


def analyze_jitted(fn, *args, cfg=None, shape=None, n_chips: int = 1, **jit_kw):
    """Convenience: jit+lower+compile then analyze (used by HGNN benches)."""
    import jax

    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    return analyze_compiled(compiled, cfg=cfg, shape=shape, n_chips=n_chips)
