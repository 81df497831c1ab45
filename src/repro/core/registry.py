"""Component registry — what "supported by the ElasticAI-Creator" means.

A *translatable component* carries up to three implementations:
  ref       — pure-jnp definition (trainable, the oracle)
  template  — the hand-optimized hardware template (Pallas kernel), the RTL
              analogue; ``None`` where plain XLA lowering is already optimal
  quantized — fixed-point / int8 variant

``Creator.validate`` walks a model config's block kinds and fails fast if a
kind has no registered component — the paper's "models must be built from
supported components" rule, enforced mechanically.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

from repro.core.types import ModelConfig


@dataclass(frozen=True)
class Component:
    name: str
    ref: str                         # dotted path of the jnp reference impl
    template: Optional[str] = None   # dotted path of the Pallas template ops
    quantized: Optional[str] = None
    notes: str = ""


_REGISTRY: Dict[str, Component] = {}


def register(c: Component) -> None:
    _REGISTRY[c.name] = c


def get(name: str) -> Component:
    if name not in _REGISTRY:
        raise KeyError(
            f"component {name!r} is not supported by the creator; "
            f"registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_components() -> Dict[str, Component]:
    return dict(_REGISTRY)


# ---------------------------------------------------------------------------
# Built-in component library
# ---------------------------------------------------------------------------

register(Component(
    "attn", ref="repro.model.attention.attn_apply",
    template="repro.kernels.flash_attention.ops",
    quantized="repro.quant.ptq",
    notes="GQA self/cross attention; flash template for long sequences"))
register(Component(
    "attn_dense", ref="repro.model.attention.attn_apply",
    template="repro.kernels.flash_attention.ops"))
register(Component(
    "moe", ref="repro.model.moe.moe_apply",
    notes="EP dispatch is collective-bound, no kernel template needed"))
register(Component(
    "mamba2", ref="repro.model.ssm.mamba_apply",
    template="repro.kernels.mamba2.ops",
    notes="mamba_apply runs ssd_chunked/ssd_step (any n_groups); the "
          "Pallas SSD template takes n_groups=1 only and is off the path"))
register(Component(
    "rwkv6", ref="repro.model.rwkv.rwkv_time_mix",
    template="repro.kernels.rwkv6.ops"))
register(Component(
    "enc", ref="repro.model.transformer._apply_enc_block"))
register(Component(
    "dec", ref="repro.model.transformer._apply_dec_block"))
register(Component(
    "lstm", ref="repro.model.lstm.lstm_apply",
    template="repro.kernels.lstm_cell.ops",
    quantized="repro.quant.qat.make_qat_lstm_apply",
    notes="the paper's own accelerator (Table I)"))
register(Component(
    "conv1d", ref="repro.model.conv1d.conv1d_apply",
    template="repro.rtl.oplib",
    notes="TCN-style depthwise sensor stack (rtl 'conv1d' hw template)"))
register(Component(
    "mlp", ref="repro.model.layers.apply_mlp",
    quantized="repro.kernels.quant_matmul.ops"))


def validate_config(cfg: ModelConfig) -> Dict[str, Component]:
    """Every block kind of this model must be a registered component."""
    from repro.model.transformer import group_structure

    used = {}
    if cfg.family in ("lstm", "conv1d"):
        used[cfg.family] = get(cfg.family)
        return used
    for kind, _ in group_structure(cfg):
        used[kind] = get(kind)
    used["mlp"] = get("mlp")
    return used
