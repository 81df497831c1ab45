"""Zamba2-7B-Instruct — 81 Mamba2 layers; two shared attention blocks,
used alternately before the Mamba layer at 13 hybrid positions.

Published config: https://huggingface.co/Zyphra/Zamba2-7B-Instruct
(``config.json``; arXiv:2411.15242). Each hybrid call k concatenates the
hidden state with the input embedding (width 2·d), runs block
``k % num_mem_blocks`` (attention at 32 heads × 224, then a GeGLU MLP
whose gate/up projection carries call k's own rank-128 adapter), and adds
the result, through a per-layer linear, to the Mamba layer's input
(``repro.model.transformer``; equations in the plain reference
``bench/configs/zamba2_7b_ref.py``).
Mamba2 reads B and C from 2 groups. ``tie_embeddings`` is assumed (the
``transformers`` default; the published config does not set it).
"""
from repro.core.types import ModelConfig, SSMConfig

#: the published ``hybrid_layer_ids``
HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)


def config() -> ModelConfig:
    return ModelConfig(
        name="zamba2-7b",
        family="hybrid",
        n_layers=81,
        d_model=3584,
        n_heads=32,
        n_kv_heads=32,
        head_dim=224,                   # attention_head_dim: 2·d / heads
        d_ff=14336,                     # shared-block MLP hidden
        vocab_size=32_000,
        norm="rmsnorm",
        act="gelu",
        rope_theta=10_000.0,
        ssm=SSMConfig(d_state=64, expand=2, headdim=64, n_groups=2,
                      chunk=256, conv_width=4),
        hybrid_layer_ids=HYBRID_LAYER_IDS,
        num_mem_blocks=2,
        adapter_rank=128,
        tie_embeddings=True,
    )


def smoke() -> ModelConfig:
    """Every part at a CPU size: both blocks (A, B, A), an adapter per call,
    2 groups, irregular hybrid positions."""
    return config().with_(
        n_layers=7, d_model=64, n_heads=4, n_kv_heads=4, head_dim=32,
        d_ff=128, vocab_size=512, vocab_pad_multiple=16,
        ssm=SSMConfig(d_state=16, expand=2, headdim=16, n_groups=2, chunk=8,
                      conv_width=4),
        hybrid_layer_ids=(1, 3, 6), adapter_rank=8,
    )
