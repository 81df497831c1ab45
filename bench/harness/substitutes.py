"""What may stand in the program's place: the control and the faults.

The comparison that decides ``correct`` has to be shown to fail. These
wrappers go where the program's callable goes (``core.Run.wrap``):

* ``reference`` the plain reference itself (a system that is correct by
  construction, under which the faults below are planted in tests);
* ``control``  the plain reference computed one precision step below the
  configuration's: weights at int4 (Q4.2, the same integer bits as the
  stated Q8.6) instead of int8 — the step that would tempt a later PR;
* ``altered``  the program, with one answer of each call changed by one
  code where it is produced, at another place in every call;
* ``half``     the program run on the first half of each batch only.

No benchmark run uses them; ``bench/control.py`` and the tests do.
"""
from __future__ import annotations

import numpy as np

#: the control's weight format: int4 with the stated format's integer bits
INT4_W = (4, 2)


def _float_out(config, codes):
    return codes / 2.0 ** int(config["formats"]["state_fmt"][1])


def reference(fn, *, config, ref, params, w_fmt=None, **_):
    """The plain reference in the program's place, at ``w_fmt`` (the
    configuration's own format when None)."""
    return lambda x: _float_out(config, ref.forward(config, params, x,
                                                    w_fmt=w_fmt))


def control(fn, **kw):
    return reference(fn, **kw, w_fmt=INT4_W)


def altered(fn, *, config, **_):
    step = 2.0 ** -int(config["formats"]["state_fmt"][1])
    calls = [0]

    def call(x):
        out = np.array(fn(x))
        flat = out.reshape(-1)
        flat[(calls[0] * 7919) % flat.size] += step     # a new place each call
        calls[0] += 1
        return out

    return call


def half(fn, **_):
    return lambda x: fn(x[:max(1, x.shape[0] // 2)])


FAULTS = {"altered": altered, "half": half}
