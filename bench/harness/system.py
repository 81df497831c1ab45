"""The system under test, built from a configuration file.

The only module of the harness that constructs program objects: the
design's ``ModelConfig`` and the fused RTL ``Deployment`` that
``Creator.translate`` returns. Weights come from the configuration's
reference module (``make_params``), drawn from the run's seed, so the
program and the reference see the same float weights and nothing else in
common.
"""
from __future__ import annotations

import typing

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy stream per purpose, from one run seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


#: stream ids, so weights and traffic never share draws
WEIGHTS, TRAFFIC = 1, 2


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.core.types import ModelConfig

    family = config["family"]
    hints = typing.get_type_hints(ModelConfig)
    sub_cls = typing.get_args(hints[family])[0]
    sub = sub_cls(**config[family])
    return ModelConfig(name=config["name"], family=family,
                       **config["model"], **{family: sub})


def formats(config: dict) -> dict:
    from repro.quant.fixedpoint import FxpFormat

    return {k: FxpFormat(*v) for k, v in config["formats"].items()}


def deployment(config: dict, params: dict):
    """``Creator.translate(target="rtl")`` with the fused emulator: the
    entry a user of the toolchain calls."""
    from repro.core.creator import Creator
    from repro.core.types import shape_table_for
    from repro.energy.hw import XC7S15
    from repro.rtl.backend import RTLOptions

    cfg = model_config(config)
    shape = next(iter(shape_table_for(cfg).values()))
    st = Creator(hw=XC7S15).build(cfg, shape)
    _, dep = Creator(hw=XC7S15).translate(
        st, target="rtl", params=params, model_flops=0.0,
        options=RTLOptions(emulator_mode="fused", **formats(config)))
    return dep


def window_shape(config: dict):
    """Per-window input shape ``(S, F)`` of the design."""
    cfg = model_config(config)
    sub = getattr(cfg, cfg.family)
    feats = getattr(sub, "in_features", None) or getattr(sub, "channels")
    return (sub.seq_len, feats)

