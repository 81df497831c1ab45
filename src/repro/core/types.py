"""Core configuration dataclasses for the ElasticAI-JAX framework.

Everything in the system — model construction, parameter schemas, sharding,
dry-run input specs, the energy model — derives from these frozen configs.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration (routed + optional shared)."""

    n_experts: int
    top_k: int
    d_expert: int                  # per-routed-expert FFN hidden size
    n_shared: int = 0              # number of always-on shared experts
    d_shared: int = 0              # hidden size of EACH shared expert
    capacity_factor: float = 1.25  # per-expert token capacity multiplier
    aux_loss_coef: float = 0.01    # load-balance auxiliary loss weight
    router_dtype: str = "float32"  # router math always runs in f32
    impl: str = "psum"             # "psum" | "a2a" | "dense" (oracle)
    first_dense: int = 0           # number of leading dense (non-MoE) layers
    d_ff_dense: int = 0            # FFN hidden of those leading dense layers


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""

    d_state: int = 64
    expand: int = 2
    headdim: int = 64
    n_groups: int = 1
    chunk: int = 256               # SSD chunk length (parallel scan blocking)
    conv_width: int = 4


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV6 ("Finch") block configuration."""

    head_size: int = 64
    decay_lora: int = 64           # rank of the data-dependent decay LoRA
    chunk: int = 128               # chunked-recurrence block length


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder stack for encoder-decoder models (whisper)."""

    n_layers: int
    n_heads: int
    d_ff: int
    n_positions: int = 1500        # precomputed frame embeddings (stub frontend)


@dataclass(frozen=True)
class LSTMConfig:
    """The paper's own model family: LSTM for time-series (traffic flow)."""

    hidden: int = 20
    n_layers: int = 1
    in_features: int = 6           # lags of the traffic-flow series
    out_features: int = 1
    seq_len: int = 6


@dataclass(frozen=True)
class Conv1dConfig:
    """TCN-style depthwise conv stack for multichannel sensor windows.

    The paper's pervasive-computing setting beyond the LSTM: ``n_blocks``
    depthwise, strided 1-D conv blocks (one ``kernel``-tap filter per
    channel) with a hard activation between, then a dense readout over the
    flattened final feature map.
    """

    channels: int = 3              # sensor channels (e.g. 3-axis IMU)
    seq_len: int = 16              # window length in samples
    kernel: int = 3                # taps per channel filter
    stride: int = 2
    n_blocks: int = 2
    out_features: int = 1
    act: str = "hard_tanh"

    def block_lens(self) -> Tuple[int, ...]:
        """Per-block output lengths: t' = (t - kernel)//stride + 1."""
        lens, t = [], self.seq_len
        for _ in range(self.n_blocks):
            t = (t - self.kernel) // self.stride + 1
            if t < 1:
                raise ValueError(
                    f"conv1d window collapses: seq_len={self.seq_len} "
                    f"kernel={self.kernel} stride={self.stride} "
                    f"n_blocks={self.n_blocks}")
            lens.append(t)
        return tuple(lens)

    @property
    def flat_features(self) -> int:
        """Input width of the dense head (last block length × channels)."""
        return self.block_lens()[-1] * self.channels


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "audio", "vlm", "hybrid", "ssm", "lstm",
            "conv1d")
BLOCK_KINDS = ("attn", "moe", "mamba2", "rwkv6", "shared_attn")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # one of FAMILIES
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default: d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    norm: str = "rmsnorm"                   # "rmsnorm" | "layernorm"
    act: str = "silu"                       # "silu" (swiglu) | "gelu" (2-matrix)
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    encoder: Optional[EncoderConfig] = None
    lstm: Optional[LSTMConfig] = None
    conv1d: Optional[Conv1dConfig] = None
    frontend: Optional[str] = None          # "audio" | "vision" (stub embeddings)
    n_frontend_tokens: int = 0              # visual/audio tokens prepended/encoded
    frontend_dim: int = 0                   # raw embedding dim from the stub
    # zamba2 hybrid: the shared attention block runs before the Mamba layer
    # at each of these indices (call k uses block k % num_mem_blocks and
    # its own rank-``adapter_rank`` MLP adapter)
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 0
    adapter_rank: int = 0
    tie_embeddings: bool = False
    vocab_pad_multiple: int = 128
    dtype: str = "bfloat16"
    # Remat policy for the layer stack: "full" | "dots" | "none"
    remat: str = "full"
    # perf levers (see EXPERIMENTS.md §Perf):
    # replicate the input embedding table (vocab-sharded gather lowers to a
    # masked-select + all-reduce pattern; the table is ~1 GB f32)
    embed_replicated: bool = False
    # chunk the CE loss over positions (needed only when the vocab cannot be
    # sharded; the chunk-slice transpose pads cotangents back to full size)
    ce_chunked: bool = True

    def __post_init__(self):
        ids = tuple(self.hybrid_layer_ids)
        object.__setattr__(self, "hybrid_layer_ids", ids)
        if ids and (list(ids) != sorted(set(ids)) or ids[0] < 0
                    or ids[-1] >= self.n_layers or self.num_mem_blocks < 1):
            raise ValueError(
                f"hybrid_layer_ids must be increasing layer indices below "
                f"n_layers={self.n_layers} with num_mem_blocks >= 1, got "
                f"{ids} and {self.num_mem_blocks}")

    # ---- derived ----------------------------------------------------------
    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return ((self.vocab_size + m - 1) // m) * m

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def block_kinds(self) -> Tuple[str, ...]:
        """Per-layer block kind sequence (length n_layers)."""
        if self.family in ("lstm", "conv1d"):
            return ()
        if self.family == "ssm":
            return ("rwkv6",) * self.n_layers
        if self.family == "hybrid":
            return ("mamba2",) * self.n_layers
        if self.family == "moe":
            assert self.moe is not None
            k = ["attn"] * self.moe.first_dense
            k += ["moe"] * (self.n_layers - self.moe.first_dense)
            return tuple(k)
        return ("attn",) * self.n_layers

    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # Rough parameter counts (used by the energy model / MODEL_FLOPS).
    def param_count(self) -> int:
        from repro.model.lm import param_schema  # local import: avoid cycle

        schema = param_schema(self)
        import math

        import jax
        from repro.model.layers import is_pspec

        return sum(
            math.prod(leaf.shape)          # python ints: no int32 overflow
            for leaf in jax.tree.leaves(schema, is_leaf=is_pspec)
        )

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k + shared experts only)."""
        total = self.param_count()
        if self.moe is None:
            return total
        m = self.moe
        per_expert = 3 * self.d_model * m.d_expert
        n_moe_layers = self.n_layers - m.first_dense
        inactive = (m.n_experts - m.top_k) * per_expert * n_moe_layers
        return total - inactive


# ---------------------------------------------------------------------------
# Input-shape config (the assigned shape grid)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str                      # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                      # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int
    #: tokens per sequence one decode step takes; more than 1 continues a
    #: prefill through the cache (the hybrid and attention paths)
    step_tokens: int = 1

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

# Paper's own workload: one LSTM inference (time-series window).
SHAPES_LSTM = {
    "infer_1": ShapeConfig("infer_1", "prefill", 6, 1),
    "train_batch": ShapeConfig("train_batch", "train", 6, 64),
}

# TCN-style sensor workload: one conv1d inference (multichannel window).
SHAPES_CONV1D = {
    "infer_1": ShapeConfig("infer_1", "prefill", 16, 1),
    "train_batch": ShapeConfig("train_batch", "train", 16, 64),
}


def shape_table_for(cfg: ModelConfig) -> dict:
    """The {name: ShapeConfig} table this arch family draws from — the one
    place the family→table mapping lives (dryrun/examples look shapes up
    here instead of re-spelling the family switch)."""
    if cfg.family == "lstm":
        return SHAPES_LSTM
    if cfg.family == "conv1d":
        return SHAPES_CONV1D
    return SHAPES


def shapes_for(cfg: ModelConfig) -> Tuple[str, ...]:
    """Which assigned shapes run for this arch (skips documented in DESIGN.md)."""
    if cfg.family in ("lstm", "conv1d"):
        return tuple(shape_table_for(cfg))
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.family in ("ssm", "hybrid"):  # sub-quadratic: run long_500k
        names.append("long_500k")
    return tuple(names)


def skipped_shapes_for(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family in ("ssm", "hybrid", "lstm", "conv1d"):
        return ()
    return ("long_500k",)


# ---------------------------------------------------------------------------
# Mesh / parallelism config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in self.axes if a in ("pod", "data"))

    @property
    def tp_axis(self) -> str:
        return "model"

    def axis_size(self, name: str) -> int:
        return dict(zip(self.axes, self.shape)).get(name, 1)


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))
SMOKE_MESH = MeshConfig((1, 1), ("data", "model"))


@dataclass(frozen=True)
class ParallelismConfig:
    """Runtime parallelism knobs (hillclimb levers)."""

    grad_compression: bool = False     # int8 ring DP all-reduce (optim.compress)
    pipeline_stages: int = 0           # >0: pod axis becomes PP
    # shard the KV cache's seq axis over "model" when kv heads don't divide
    # tp (otherwise the cache is replicated 16×) — §Perf cell B lever
    seq_shard_decode: bool = False
    scan_layers: bool = False          # scan (fast compile) vs unroll (exact cost)
    param_dtype: str = "float32"       # master params
    compute_dtype: str = "bfloat16"
    # attention implementation: "ref" (XLA, exact cost) | "flash" (Pallas
    # template; TPU execution) | "template_stub" (negligible-cost stand-in
    # for dry-run lowering; the hillclimb adds the template's analytic cost)
    attn_impl: str = "ref"
    # grouped-GQA attention: contract q-head groups against UNREPEATED K/V
    # instead of materializing H/KV-times-repeated K/V (hillclimb lever;
    # exactness asserted in tests/test_gqa_grouped.py)
    gqa_grouped: bool = False
