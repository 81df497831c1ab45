"""Operations and bytes of each design window and each kernel call.

Computed from the design's shapes in its configuration file, never from
the program, so the yardstick does not move when program code moves.

Operations follow the paper's convention (OP = 2 x MAC, ElasticAI,
arXiv:2409.09044, Table I): ``lstm_flops`` / ``conv1d_flops`` of
``src/repro/model/lstm.py`` and ``src/repro/model/conv1d.py`` copied
here. For elastic-lstm that is 20,680 OP per window:
6 steps x (2 x 21 x 80 + 80) + 2 x 20 x 1.

Bytes are what a kernel call must move at least: each operand and result
once, at its Q-format's width rounded up to whole bytes (8-bit codes are
1 byte, 16-bit 2, the int32 bias words 4), and the ROM tables once.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

#: kernel -> pattern matched against the short name of a device operation
#: that is a custom call (a Pallas kernel). The TPU trace names each
#: operation by its HLO instruction, and a Pallas kernel's instruction by
#: the jitted function that wraps it: ``lstm_window_int.1``,
#: ``mac_int_pallas.3``.
KERNELS: Dict[str, str] = {
    "lstm_window_int": r"^lstm_window_int(\.\d+)?$",
    "mac_int": r"^mac_int_pallas(\.\d+)?$",
}


def _nbytes(fmt) -> int:
    return -(-int(fmt[0]) // 8)


def ops_per_window(config: dict) -> int:
    """OP (2 x MAC, plus the elementwise state ops) of one window."""
    fam = config["family"]
    if fam == "lstm":
        c = config["lstm"]
        total = 0
        for i in range(c["n_layers"]):
            d_in = c["in_features"] if i == 0 else c["hidden"]
            per_step = 2 * (d_in + c["hidden"]) * 4 * c["hidden"] \
                + 4 * c["hidden"]
            total += per_step * c["seq_len"]
        return total + 2 * c["hidden"] * c["out_features"]
    if fam == "conv1d":
        c = config["conv1d"]
        total, t = 0, c["seq_len"]
        for _ in range(c["n_blocks"]):
            t = (t - c["kernel"]) // c["stride"] + 1
            total += 2 * t * c["kernel"] * c["channels"] + t * c["channels"]
        return total + 2 * t * c["channels"] * c["out_features"]
    raise ValueError(f"no operation count for family {fam!r}")


def kernel_calls(config: dict, batch: int) -> List[Tuple[str, int, int]]:
    """``(kernel, ops, bytes)`` of each Pallas call one fused dispatch of
    ``batch`` windows makes, in program order."""
    f = config["formats"]
    a, w, s = (_nbytes(f[k]) for k in ("act_fmt", "w_fmt", "state_fmt"))
    fam = config["family"]
    calls = []
    if fam == "lstm":
        c = config["lstm"]
        h, S = c["hidden"], c["seq_len"]
        act_bits = int(f["act_fmt"][0])
        for i in range(c["n_layers"]):
            d_in = c["in_features"] if i == 0 else h
            ops = batch * S * (2 * (d_in + h) * 4 * h + 4 * h)
            nbytes = (batch * S * d_in * a + (d_in + h) * 4 * h * w
                      + 4 * h * 4 + 2 * (2 ** act_bits) * a
                      + batch * S * h * a)
            calls.append(("lstm_window_int", ops, nbytes))
        n_in, n_out = h, c["out_features"]
    elif fam == "conv1d":
        c = config["conv1d"]
        t, k, ch = c["seq_len"], c["kernel"], c["channels"]
        for _ in range(c["n_blocks"]):
            t = (t - k) // c["stride"] + 1
            rows = batch * t
            calls.append(("mac_int", 2 * rows * k * ch,
                          rows * k * ch * a + k * ch * w + ch * 4
                          + rows * ch * a))
        n_in, n_out = t * ch, c["out_features"]
    else:
        raise ValueError(f"no kernel calls for family {fam!r}")
    calls.append(("mac_int", 2 * batch * n_in * n_out,
                  batch * n_in * a + n_in * n_out * w + n_out * 4
                  + batch * n_out * s))
    return calls


def per_dispatch(config: dict, batch: int) -> Dict[str, Dict[str, int]]:
    """Per kernel: calls, ops and bytes of one dispatch, summed."""
    out: Dict[str, Dict[str, int]] = {}
    for name, ops, nbytes in kernel_calls(config, batch):
        row = out.setdefault(name, {"calls": 0, "ops": 0, "bytes": 0})
        row["calls"] += 1
        row["ops"] += ops
        row["bytes"] += nbytes
    return out


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peaks: dict) -> Tuple[float, str]:
    """Least time the chip could take over the time taken, in percent,
    and which bound sets that least time."""
    t_ops = ops / peaks["int8_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_mem else "memory"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
