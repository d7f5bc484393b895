"""``bench/trace.py`` on a small trace recorded on a TPU v5e.

``testdata/tiny.xplane.pb`` holds three iterations of: host span
``bench.dispatch`` (3 ms of host sleep, then a jitted call: a
convolution fusion, a slice, a Pallas kernel ``tiny_kernel`` and a
reduction), host span ``bench.readback`` (reads the result), and 2 ms
of host sleep outside any span.  ``testdata/tiny.hlo.txt`` is the
compiled program.  The numbers below were read off the trace's events
by hand (device ``XLA Ops`` line and host spans, in ns).
"""
import pytest
from jax.profiler import ProfileData

import harness
from benchutil import BENCH

# host spans: dispatch 4,338,120 + 3,517,160 + 3,451,640 ns; readback
# 1,374,350 + 848,030 + 760,450 ns; first start 41,377,439, last end
# 60,584,479
WINDOW_NS = 60_584_479 - 41_377_439
DISPATCH_NS = 4_338_120 + 3_517_160 + 3_451_640
READBACK_NS = 1_374_350 + 848_030 + 760_450
# device ops per iteration (copy-start, copy-done, fusion.5 (the
# convolution), slice.2, tiny_kernel.1, reduce_sum.15), all inside the
# iteration's dispatch span
ITERS = [(13, 3, 12_693, 172, 25, 366), (13, 2, 12_570, 172, 25, 365),
         (13, 2, 12_681, 173, 25, 366)]
BUSY_NS = sum(sum(it) for it in ITERS)
CONV_NS = sum(it[2] for it in ITERS)


@pytest.fixture(scope="module")
def reduced():
    trace = harness.load_module(BENCH / "trace.py")
    data = ProfileData.from_file(str(BENCH / "testdata" / "tiny.xplane.pb"))
    hlo = (BENCH / "testdata" / "tiny.hlo.txt").read_text()
    return trace.reduce(data, 1, hlo)


def test_window_busy_and_idle(reduced):
    assert reduced.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert reduced.busy_s[0] == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)
    idle = harness.metric("device.idle_frac").reduce({"reduced": reduced})
    assert idle == pytest.approx(100 * (1 - BUSY_NS / WINDOW_NS), rel=1e-9)


def test_kernel_and_kind_sums(reduced):
    assert reduced.op_calls("tiny_kernel") == 3
    assert reduced.op_time("tiny_kernel") == pytest.approx(75e-9, abs=1e-15)
    assert reduced.kind_time_s[0]["convolution"] == pytest.approx(
        CONV_NS * 1e-9, abs=1e-15)
    assert reduced.kind_time_s[0]["kernel"] == pytest.approx(75e-9,
                                                            abs=1e-15)


def test_idle_gaps_by_host_span(reduced):
    gaps = reduced.gaps
    assert gaps["dispatch"] == pytest.approx((DISPATCH_NS - BUSY_NS) * 1e-9,
                                             abs=1e-12)
    assert gaps["readback"] == pytest.approx(READBACK_NS * 1e-9, abs=1e-12)
    outside = WINDOW_NS - DISPATCH_NS - READBACK_NS
    assert gaps["outside spans"] == pytest.approx(outside * 1e-9, abs=1e-12)
    top = reduced.breakdown()
    assert top["idle_gaps"][0][0] == "dispatch"
    assert top["device_ops"][0][0] == "fusion.5 (convolution)"


def test_kinds_from_hlo_text():
    trace = harness.load_module(BENCH / "trace.py")
    kinds = trace.hlo_kinds((BENCH / "testdata" / "tiny.hlo.txt").read_text())
    assert kinds["fusion.5"] == "convolution"
    assert kinds["tiny_kernel.1"] == "kernel"
    assert kinds["reduce_sum.15"] == "other"
