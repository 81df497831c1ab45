"""The ``zamba2-decode`` cell on the CPU at test size: the cell's own kind
(``bench/kinds/lm_decode.py``) and reference, on a configuration with the
smoke preset's widths and a short context, so every session goes back to
its snapshot several times within the window.

The program reads correct; one kept logit row altered, one session's
greedy pick altered, one session left unrestored at its reset, and the
float8 control each read not correct. At the cell's own size set-up spreads
the sessions over their lives.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import run as bench_run
from bench.harness import core, spec

NAME = "zamba2-decode"
#: the smoke preset's widths (``repro.configs.zamba2_7b.smoke``) in the
#: configuration file's keys; unit-scale weights so each part shows
SMALL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "attention_head_dim": 32, "intermediate_size": 128, "n_mamba_heads": 8,
    "mamba_headdim": 16, "mamba_d_state": 16, "chunk_size": 8,
    "vocab_size": 512, "adapter_rank": 8, "num_hidden_layers": 7,
    "hybrid_layer_ids": [1, 3, 6], "initializer_range": 0.125,
    "weights": {"dtype": "float32"},
    # f32 program against the f32 reference: the sums' order differs only
    "tolerance": {"atol": 1e-3, "rtol": 1e-3},
}
TRAFFIC = {"batch": 2, "prompt": 8, "context": 12, "prefill_batch": 1,
           "extend": 2, "kept_steps": 3, "kept_within": 6}


def _cell():
    cell = spec.load_cell(NAME)
    return dataclasses.replace(cell, config={**cell.config, **SMALL})


def _run(wrap=None, seconds=0.6, trace=False, seed=2 ** 31 + 9):
    return bench_run.run_cell(_cell(), seed=seed, seconds=seconds,
                              trace=trace, wrap=wrap,
                              traffic_overrides=TRAFFIC)


def _wrapped(cls):
    return lambda make, **_: (lambda: cls(make()))


class _Planted:
    """The program, with one fault planted by a subclass."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class _AlteredRow(_Planted):
    """Logits row 0 of every step shifted by 0.5 (every kept step has it)."""

    def step(self):
        logits, ids = self.inner.step()
        return logits.at[0].add(0.5), ids


class _AlteredPick(_Planted):
    """Session 1's greedy pick of every step moved to the next id, and fed
    so: the logits stay those of the ids the session was given."""

    def step(self):
        logits, ids = self.inner.step()
        ids = ids.at[1, 0].set((ids[1, 0] + 1) % SMALL["vocab_size"])
        self.inner.ids = ids
        return logits, ids


class _NoRestore(_Planted):
    """Session 0 left out of every reset."""

    def reset(self, mask):
        mask = np.array(mask)
        mask[0] = False
        self.inner.reset(mask)


def test_program_reads_correct_across_resets():
    out = _run()
    assert out["correct"], out["checks"]
    per_epoch = TRAFFIC["context"] - TRAFFIC["prompt"]
    assert out["attempted"] > 2 * per_epoch * TRAFFIC["batch"]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 9, 3141592653])
def test_sessions_start_spread_over_their_lives(seed):
    """At the cell's own traffic: each session starts a multiple of
    ``extend`` past its prompt, inside its cache, the sessions spread over
    the whole life, and one always starts in the last ``extend``
    positions (so a 10 s window sees a reset)."""
    cell = spec.load_cell(NAME)
    tr = cell.traffic
    at = int(tr["prompt"]) + cell.kind.stretch_lengths(
        np.random.default_rng(seed), tr)
    assert len(at) == tr["batch"]
    assert np.all(at % tr["extend"] == 0)
    assert at.min() >= tr["prompt"] and at.max() < tr["context"]
    assert at.max() == tr["context"] - tr["extend"]
    assert len(set(at.tolist())) >= tr["batch"] // 2


@pytest.mark.parametrize("fault", [_AlteredRow, _AlteredPick, _NoRestore])
def test_fault_reads_not_correct(fault):
    out = _run(_wrapped(fault))
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatched_codes"]["value"] > 0


def test_float8_control_reads_not_correct():
    cell = _cell()
    out = bench_run.run_cell(cell, seed=2 ** 31 + 9, seconds=0.3,
                             trace=False, wrap=cell.kind.control,
                             traffic_overrides=TRAFFIC)
    assert not out["correct"]
    assert out["checks"]["mismatched_codes"]["value"] > 100


def test_traced_window_feeds_the_program_metrics():
    """In a traced window the kind keeps the program's ``xla.*`` spans and
    counters (``repro.obs.capture``); each new reader turns them into a
    number (here at the chip's peaks, though the run is on the CPU)."""
    cell = _cell()
    run = core.Run(cell=cell, seed=3, seconds=0.3, trace=True,
                   traffic_overrides=TRAFFIC)
    run.peaks = spec.peaks_for("TPU v5 lite")
    st = cell.kind.setup(run)
    cell.kind.window(run, st)
    cell.kind.payload(st)
    spans = [s for s in run.stats["obs"].spans if s.name == "xla.call"]
    assert spans and all(s.attrs["kind"] == "decode" for s in spans)
    for name in ("lm_mfu.decode", "lm_hbm_roofline.decode",
                 "decode_call_ms.zamba2"):
        v = cell.readers[name].read(run)
        assert v is not None and v > 0, name
