"""The trace reduction and the roofline / utilisation arithmetic, on a
synthesised trace (``fixtures/xspace.pbtxt``) and hand-computed counts."""
from __future__ import annotations

import pathlib
import sys
import warnings

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import counts  # noqa: E402
from bench.harness import readers, spec, tracing  # noqa: E402

FIXTURE = pathlib.Path(__file__).with_name("fixtures") / "xspace.pbtxt"
V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        profile = ProfileData.from_text_proto(FIXTURE.read_text())
        return tracing.reduce_profile(profile, counts.KERNELS)


def test_busy_and_idle_share(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(10e-6)
    assert summary.busy_s == pytest.approx(5e-6)      # union, clipped
    assert summary.idle_share == pytest.approx(0.5)


def test_kernel_time_of_custom_calls_by_instruction_name(summary):
    # copy.4 names the LSTM kernel's output among its operands and is not
    # counted; the kernel's overlap with fusion.1 still counts to it
    assert summary.kernel_s["lstm_window_int"] == pytest.approx(1.5e-6)
    assert summary.kernel_calls == {"lstm_window_int": 1, "mac_int": 2}
    assert summary.kernel_s["mac_int"] == pytest.approx(1.5e-6)


def test_top_ops_clip_to_window(summary):
    ops = dict(summary.top_ops)
    assert ops == {"fusion.1": pytest.approx(2e-6),
                   "lstm_window_int.1": pytest.approx(1.5e-6),
                   "mac_int_pallas.1": pytest.approx(1.5e-6),
                   "copy.4": pytest.approx(0.5e-6)}   # 1.5 us, half outside
    assert summary.top_ops[0][0] == "fusion.1"        # module line ignored


def test_idle_gaps_labelled_by_host_annotation(summary):
    assert summary.idle_gaps == [
        ("bench.fetch", pytest.approx(2e-6)),
        ("bench.call", pytest.approx(2e-6)),
        ("other", pytest.approx(1e-6))]


def test_window_must_be_annotated():
    with pytest.raises(RuntimeError, match="bench.window"):
        tracing.summarize({}, [], counts.KERNELS)


def lstm_config():
    return spec.read_json(ROOT / "bench" / "configs" / "elastic_lstm.json")


def test_ops_per_window_is_the_papers_count():
    # 6 steps x (2 x 21 x 80 + 80) + 2 x 20 x 1
    assert counts.ops_per_window(lstm_config()) == 20_680
    # a depthwise conv1d design (3 channels, window 16, two stride-2
    # blocks of 3 taps): blocks of 7 and 3 steps,
    # 2*7*3*3+7*3 + 2*3*3*3+3*3, head 2*9
    conv = {"family": "conv1d",
            "conv1d": {"channels": 3, "seq_len": 16, "kernel": 3,
                       "stride": 2, "n_blocks": 2, "out_features": 1}}
    assert counts.ops_per_window(conv) == 147 + 63 + 18


def test_kernel_calls_of_a_4096_lstm_dispatch():
    per = counts.per_dispatch(lstm_config(), 4096)
    assert per["lstm_window_int"] == {
        "calls": 1, "ops": 4096 * 20_640,
        # x 24576 + W 1680 + b 320 + two ROMs 512 + h sequence 491520
        "bytes": 518_608}
    # linear head: 2*4096*20 ops; h 81920 + W 20 + b 4 + y (int16) 8192
    assert per["mac_int"] == {"calls": 1, "ops": 163_840, "bytes": 90_136}


def test_roofline_share_by_hand():
    share, bound = counts.roofline_share(4096 * 20_640, 518_608, 2e-3, V5E)
    # bytes bind: 518608 / 819e9 = 6.3322e-7 s of 2 ms
    assert bound == "memory"
    assert share == pytest.approx(100 * 518_608 / 819e9 / 2e-3)
    assert share == pytest.approx(0.031661, rel=1e-4)
    share, bound = counts.roofline_share(393e9, 1.0, 2e-3, V5E)
    assert bound == "compute" and share == pytest.approx(50.0)


class _Run:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_kernel_roofline_reader_counts_calls_in_the_window(summary):
    run = _Run(summary=summary, config=lstm_config(), peaks=V5E,
               traffic={"batch": 4096})
    # one lstm kernel call of 4096 windows in 1.5 us of device time
    want = 100 * (518_608 / 819e9) / 1.5e-6
    assert readers.kernel_roofline(run, "lstm_window_int") == \
        pytest.approx(want)
    # two head calls (two dispatches) in 1.5 us
    want = 100 * (2 * 90_136 / 819e9) / 1.5e-6
    assert readers.kernel_roofline(run, "mac_int") == pytest.approx(want)
    run.summary = tracing.Summary(window_s=1.0, busy_s=0.5, n_devices=1)
    assert readers.kernel_roofline(run, "mac_int") is None


def test_emu_mfu_by_hand():
    run = _Run(config=lstm_config(), peaks=V5E,
               cell=_Run(chips=1),
               stats={"windows": 1_500_000, "elapsed_s": 1.0})
    # 20680 OP x 1.5e6 windows/s over 393e12 OP/s
    assert readers.mfu_percent(run) == pytest.approx(0.0078931, rel=1e-4)
    run.cell.chips = 4
    assert readers.mfu_percent(run) == pytest.approx(0.0078931 / 4,
                                                     rel=1e-4)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")
