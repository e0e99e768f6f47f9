"""Config dataclasses for the repro framework.

Two config families:
  * ``ModelConfig`` — the 10 assigned LM architectures (+ reduced smoke
    variants).  One module per arch under ``repro.configs``; each exposes
    ``config()`` (full, dry-run only) and ``reduced()`` (CPU smoke).
  * ``HGNNConfig`` — the paper's HGNN workloads (RGCN / HAN / MAGNN / GCN on
    IMDB / ACM / DBLP / Reddit-like).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# LM architecture configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block config (Switch/GShard-style einsum dispatch)."""

    n_experts: int
    top_k: int
    d_ff_expert: int
    # Arctic runs a dense FFN *in parallel* with the MoE FFN ("dense residual").
    dense_residual_ff: int = 0
    capacity_factor: float = 1.25


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD — state-space duality) block config."""

    d_state: int
    head_dim: int = 64
    expand: int = 2
    d_conv: int = 4
    chunk: int = 256  # SSD chunk length (intra-chunk quadratic, inter-chunk scan)


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0  # 0 -> d_model // n_heads
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # Sliding-window attention width; 0 = full causal attention.
    sliding_window: int = 0
    # Encoder-decoder (seamless-m4t): n_layers applies to each side.
    enc_layers: int = 0
    dec_layers: int = 0
    # Modality frontend stub: number of precomputed embeddings prepended.
    frontend: Optional[str] = None  # vision | audio
    n_frontend_embeds: int = 0
    # zamba2: one shared attention block applied every `shared_attn_period`
    # Mamba2 layers (weights shared across applications).
    shared_attn_period: int = 0
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # Optimizer / memory knobs (needed so the biggest archs fit the pod).
    optimizer: str = "adamw"  # adamw | adafactor
    opt_state_dtype: str = "float32"
    # 'full' is the safe default: 'dots' saves every no-batch-dim matmul
    # output across the layer scan (8 GiB/step f32 on smollm alone) — see
    # EXPERIMENTS.md §Perf for the measured comparison.
    remat: str = "full"  # none | dots | full
    # q/kv-chunk length for the online-softmax (flash-style) attention path.
    # 512 keeps the fp32 score tile (B_local x H_local x cq x ck) HBM-friendly
    # even when heads cannot shard (see EXPERIMENTS.md §Perf smollm study).
    attn_chunk: int = 512
    # Pallas kernels are TPU-only; dry-run path keeps this False (CPU backend
    # cannot compile TPU custom calls). Tests exercise kernels in interpret mode.
    use_pallas: bool = False
    # --- beyond-paper perf knobs (hillclimb; see EXPERIMENTS.md §Perf) ---
    # Pad attention heads up to a multiple of the 'model' axis so GSPMD does
    # not fall back to uneven/halo sharding (arctic: 56 -> 64).
    pad_heads_to_mesh: bool = False
    # Shard the decode KV cache's sequence dim over 'model' (flash-decode).
    decode_kv_shard_seq: bool = True
    # FSDP (ZeRO-3) over the 'data' axis in addition to TP over 'model'.
    # Required for the 76B/480B archs' optimizer state to fit a pod.
    fsdp: bool = True
    # FSDP also on expert weights (arctic: needed; phi3.5: EP alone fits and
    # skipping saves per-layer expert all-gathers — §Perf H-B2).
    fsdp_experts: bool = True
    # Megatron-style sequence parallelism: residual stream sharded over
    # 'model' at layer boundaries, so the per-layer activations saved by the
    # remat'd layer scan divide by the model axis (internvl2 train: 91 GB ->
    # 5.7 GB of carries per device).
    seq_shard_activations: bool = True
    # Gradient-accumulation microbatches per train step. Divides the
    # per-microbatch activation transients (the full-seq fp32 tensors at TP
    # matmul boundaries) — how the 76B/480B train cells fit 16 GB HBM.
    n_microbatches: int = 1

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# Archs for which long_500k is runnable (sub-quadratic decode path);
# all other archs are pure full-attention -> skip recorded in DESIGN.md §4.
LONG_CONTEXT_ARCHS = ("mamba2-2.7b", "zamba2-1.2b", "h2o-danube-3-4b")


def long_context_supported(cfg: ModelConfig) -> bool:
    return cfg.family in ("ssm", "hybrid") or cfg.sliding_window > 0


# ---------------------------------------------------------------------------
# HGNN configs (the paper's workloads)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HGNNConfig:
    model: str = "han"  # rgcn | han | magnn | gcn
    dataset: str = "imdb"  # imdb | acm | dblp | reddit
    hidden: int = 64
    n_classes: int = 8
    # attention heads in Neighbor Aggregation: HAN splits `hidden` over
    # them; MAGNN's are each `hidden` wide, concatenated to n_heads*hidden
    n_heads: int = 8
    attn_hidden: int = 128  # semantic-attention hidden dim
    max_degree: int = 64  # padded-neighbor cap (TPU-friendly dense layout)
    # MAGNN instances sampled per target node on the full-graph path;
    # 0 keeps every instance (sampled serving then caps at `fanout`)
    max_instances: int = 16
    # Optimized (beyond-paper / guideline) execution path:
    #   stacked subgraphs (inter-subgraph parallelism), concat-free SA,
    #   optionally the fused GAT-NA / FP+NA kernels.
    fused: bool = False
    use_pallas: bool = False
    # Degree-bucketed padded NA layout: >1 bins rows into that many K-caps
    # (core/metapath.py bucket_padded) instead of one K=max_degree pad;
    # 0/1 keeps the single stacked [P, N, K] layout. Fused path only
    # (HAN's stacked metapaths and RGCN's per-relation tables). MAGNN bins
    # its [N, I, L] instance tables by instance count the same way; there
    # 0 picks 8 buckets when every instance is kept and 1 keeps one table.
    degree_buckets: int = 0
    # Fused NA→SA epilogue (inter-stage data reuse): the semantic-score
    # pass-1 partial accumulates inside the NA kernel while each z tile is
    # still in VMEM, saving one full [P, N, D] HBM read. Stacked layout only.
    fuse_na_sa: bool = False
    # Graph-partitioned multi-host execution (repro.dist.partition): >= 1
    # splits the vertex/feature tables into that many edge-cut partitions —
    # FP/NA run per-partition on local shards with an explicit halo feature
    # exchange between them. 0 keeps the single-table execution. Needs the
    # stacked (HAN) / padded (RGCN) / instances (MAGNN) NA layouts.
    partitions: int = 0
    # Stacked FP->NA->SA layers (real deployments run 2-3; the training
    # characterization, arXiv:2407.11790, measures the stage mix shifting
    # with depth). 1 = the paper's single pass, bit-exact with the
    # pre-multi-layer path. The graph-side index tables are layer-invariant
    # (built once in prepare()); each extra layer adds its own FP/NA/SA
    # params and, when partitioned, re-exchanges the updated halo features.
    layers: int = 1
    # Request-path serving (repro.serve.sampler): >= 1 declares the plan
    # sampled-minibatch capable with that per-hop neighbor fan-out cap.
    # 0 keeps the full-graph execution (prepare() builds the whole graph).
    fanout: int = 0
    # Shape-bucket ladder for sampled batches: (t_cap, f_cap) rungs the
    # sampler pads every minibatch to, so the jitted executor compiles one
    # forward per rung at warmup and never recompiles while serving.
    # () = a small automatic ladder derived from fanout/layers.
    sample_ladder: Tuple[Tuple[int, int], ...] = ()
    # Hot-feature residency (repro.core.residency): >= 1 keeps that many
    # hot rows per node type resident in a degree-ordered feature cache.
    # Every gather path consults it — NA neighbor tables remap into the
    # cache-extended source pool, the partitioned arm's hot halo rows skip
    # the exchange, and the serving engine's per-step sampled frontier
    # runs against a live pinned cache. 0 = no cache (every gather re-reads
    # HBM). Bit-exact by construction: cache rows are bitwise row copies.
    cache_rows: int = 0
    # Async stage-graph schedule (core/plan.py ScheduleSpec): >= 1 runs the
    # executor's dependency DAG with that many stages in flight — the halo
    # exchange overlaps NA over owned rows, per-metapath NA stages dispatch
    # concurrently (merge at SA), and serving prefetches the next slot
    # batch while the device computes. 1 is the serial-degenerate schedule
    # (every stage blocked — the parity baseline); 0 keeps the strict
    # serial stage loop with no schedule at all. Bit-exact either way:
    # overlap changes when stages run, never what they compute.
    overlap: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.layers < 1:
            raise ValueError(
                f"HGNNConfig.layers must be >= 1 (got {self.layers})")

    def replace(self, **kw) -> "HGNNConfig":
        return dataclasses.replace(self, **kw)
