"""Bulk evaluation: a dataset of windows through one fused Deployment.

Traffic keys: ``batch`` windows per call, ``ring`` distinct batches drawn
in set-up and cycled, ``scale`` of the normal window contents.

The window keeps one call in flight ahead of the fetch: issue batch i+1,
fetch batch i to the host, repeat. ``windows_per_s`` is every window
fetched over the time from the first issue to the last fetch. Every
fetched batch is compared with the plain reference after the window.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List

import numpy as np

from bench.harness import check, core, loadgen, system


@dataclass
class State:
    params: dict
    call: Callable
    ring: List[np.ndarray]


def setup(run) -> State:
    cfg, tr = run.config, run.traffic
    params = run.cell.ref.make_params(cfg, system.rng_for(run.seed,
                                                          system.WEIGHTS))
    call = run.system(system.deployment(cfg, params), params=params)
    rng = system.rng_for(run.seed, system.TRAFFIC)
    shape = system.window_shape(cfg)
    ring = [loadgen.windows(rng, int(tr["batch"]), shape, float(tr["scale"]))
            for _ in range(int(tr["ring"]))]
    for x in ring[:2]:                       # compile, then one warm call
        np.asarray(call(x))
    return State(params, call, ring)


def window(run, st: State):
    """Returns ``(end-to-end values, kept outputs)``."""
    kept, elapsed = core.one_in_flight(run, st.call, st.ring, lambda h: h)
    n = len(kept) * int(st.ring[0].shape[0])
    run.stats.update(windows=n, elapsed_s=elapsed, dispatches=len(kept))
    return {"windows_per_s": n / elapsed}, kept


def check_outputs(run, params_ring, kept) -> dict:
    """The numbers compared, after the window: every fetched batch against
    the reference of its ring slot."""
    params, ring = params_ring
    want, bad = {}, 0
    for slot, host in kept:
        if slot not in want:
            want[slot] = run.cell.ref.forward(run.config, params, ring[slot])
        bad += check.mismatches(check.codes(host, run.config), want[slot])
    return {"mismatched_codes": bad, "missing_answers": 0,
            "attempted": len(kept) * int(ring[0].shape[0]), "failed": 0}


def payload(st: State):
    """What the check needs once the program's state is freed."""
    return st.params, st.ring
