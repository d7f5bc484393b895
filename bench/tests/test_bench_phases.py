"""``bench/phases.py`` and the phase metrics on a small trace recorded on
a TPU v5e (``testdata/record_phases.py`` made it).

``testdata/phases.xplane.pb`` holds three iterations of: host span
``bench.call`` (2 ms of host sleep, then ``fed.run.dispatch``: 2 ms of
sleep and a jitted three-step ``lax.scan`` whose body holds
``fed.local_train`` with ``fed.psm`` nested in its loss, ``fed.uplink``
and an unscoped update; then ``fed.run.readback``, the read of the
result), and 2 ms of host sleep outside any span.
``testdata/phases.hlo.txt`` is the compiled program.  On the chip XLA
fused the psm and uplink instructions into fusions named by
``fed.local_train`` or by no scope, so the device ran local_train and
unscoped instructions only.  The numbers below were read off the trace's
events and the HLO's ``op_name`` metadata by hand (ns).
"""
import dataclasses

import pytest
from jax.profiler import ProfileData

import harness
from benchutil import BENCH

CALL = [(46_226_596, 52_602_355), (55_022_195, 61_083_924),
        (63_797_244, 69_657_624)]
DISPATCH = [(48_505_755, 51_244_825), (57_195_345, 59_851_985),
            (65_977_634, 68_651_384)]
READBACK = [(51_252_765, 52_601_675), (59_857_774, 61_083_394),
            (68_655_734, 69_656_893)]
WINDOW_NS = 69_657_624 - 46_226_596


def _total(spans):
    return sum(e - s for s, e in spans)


# device ops of each iteration, all inside its fed.run.dispatch span:
# before the loop copy-start.1, copy-start.3, custom-call, copy-done.1,
# copy-done.3; the while (control); after it copy-start, copy-start.2,
# copy-start.4, copy-done, copy-done.2, copy-done.4
PRE = [(5, 3, 2, 413, 79), (5, 2, 2, 653, 54), (6, 2, 2, 411, 93)]
WHILE = [3944, 3946, 3946]
POST = [(5, 6, 6, 297, 86, 3), (6, 6, 6, 296, 83, 2),
        (6, 6, 7, 295, 85, 3)]
# inside each while, three steps of multiply_reduce_fusion.2 and
# fusion.19 (op_name .../fed.local_train/...) and dynamic_update_slice.2,
# broadcast_multiply_fusion.2, multiply_add_fusion.2 (no fed.* name)
LOCAL = [[(471, 312), (471, 312), (472, 312)],
         [(469, 312), (474, 312), (471, 313)],
         [(469, 312), (472, 312), (471, 312)]]
LOOSE = [[(136, 7, 27), (136, 7, 27), (137, 7, 26)],
         [(137, 7, 27), (136, 7, 26), (135, 8, 27)],
         [(136, 7, 27), (136, 7, 26), (135, 7, 27)]]
BUSY_NS = sum(map(sum, PRE)) + sum(WHILE) + sum(map(sum, POST))
LOCAL_NS = sum(sum(step) for it in LOCAL for step in it)
UNSCOPED_NS = (sum(map(sum, PRE)) + sum(map(sum, POST))
               + sum(sum(step) for it in LOOSE for step in it))


@pytest.fixture(scope="module")
def phases():
    return harness.load_module(BENCH / "phases.py")


@pytest.fixture(scope="module")
def data():
    return ProfileData.from_file(str(BENCH / "testdata" / "phases.xplane.pb"))


@pytest.fixture(scope="module")
def hlo():
    return (BENCH / "testdata" / "phases.hlo.txt").read_text()


@pytest.fixture(scope="module")
def reduced(phases, data, hlo):
    return phases.trace.reduce(data, 1, hlo)


def test_phase_sums_and_unscoped(phases, reduced, hlo):
    times, = phases.phase_time_s(reduced, phases.hlo_scopes(hlo))
    assert set(times) == {"local_train", "unscoped"}
    assert times["local_train"] == pytest.approx(LOCAL_NS * 1e-9, abs=1e-15)
    assert times["unscoped"] == pytest.approx(UNSCOPED_NS * 1e-9, abs=1e-15)
    # the phases and unscoped split the non-control op time, to within
    # the rounding of a float sum
    assert sum(times.values()) == pytest.approx(
        sum(reduced.op_time_s[0].values()), abs=1e-18)
    assert reduced.busy_s[0] == pytest.approx(BUSY_NS * 1e-9, abs=1e-15)


@pytest.mark.parametrize("instruction, phase", [
    ("multiply_reduce_fusion.2", "local_train"),   # .../fed.local_train/jvp()
    ("fusion.19", "local_train"),       # .../fed.local_train/transpose(jvp())
    ("tanh.10", "psm"),                 # .../fed.local_train/jvp(fed.psm)
    ("mul.31.clone.3", "psm"),  # .../fed.local_train/transpose(jvp(fed.psm))
    ("reduce_sum.13", "uplink"),        # .../closed_call/fed.uplink
    ("reduce_sum.12", "uplink"),        # fed.uplink/reduce_sum
    ("dynamic_update_slice.2", "unscoped"),
    ("while", "unscoped"),
    ("copy-done.1", "unscoped"),        # no metadata
])
def test_hlo_scopes_resolve_the_innermost_name(phases, hlo, instruction,
                                               phase):
    assert phases.hlo_scopes(hlo)[instruction] == phase


@pytest.mark.parametrize("op_name, phase", [
    ("jit(f)/vmap(fed.local_train)/while/body/jvp(fed.psm)/mul", "psm"),
    ("jit(f)/fed.local_train/transpose(jvp(fed.psm))/jvp(jit(clip))/eq",
     "psm"),
    ("jit(f)/fed.uplink/fed.noise/add", "noise"),
    ("fed.local_train/mul;fed.uplink/broadcast_in_dim", "uplink"),
    ("jit(f)/feed.psm/mul", "unscoped"),
    ("jit(f)/while/body/add", "unscoped"),
])
def test_scope_of_op_names(phases, op_name, phase):
    assert phases.scope_of(op_name) == phase


def test_idle_gaps_go_to_the_innermost_span(phases, data):
    host = phases.reduce_host(data)
    assert host.window == (CALL[0][0], CALL[-1][1])
    idle = {"fed.run.dispatch": _total(DISPATCH) - BUSY_NS,
            "fed.run.readback": _total(READBACK),
            "bench.call": _total(CALL) - _total(DISPATCH) - _total(READBACK),
            "outside spans": WINDOW_NS - _total(CALL)}
    assert set(host.gaps) == set(idle)
    for name, ns in idle.items():
        assert host.gaps[name] == pytest.approx(ns * 1e-9, abs=1e-12), name
    assert sum(host.gaps.values()) == pytest.approx(
        (WINDOW_NS - BUSY_NS) * 1e-9, abs=1e-12)
    assert host.span_s("fed.run.dispatch") == pytest.approx(
        _total(DISPATCH) * 1e-9, abs=1e-12)


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Data:
    planes: list


def _without_program_spans(data):
    """The trace with every ``fed.run.*`` host event left out."""
    return _Data([_Plane(p.name, [
        _Line(ln.name, [ev for ev in ln.events
                        if not ev.name.startswith("fed.run.")])
        for ln in p.lines]) for p in data.planes])


def test_existing_numbers_ignore_program_spans(phases, data, hlo, reduced):
    """Window, busy, idle gaps, kinds and kernels, which the five metrics
    of the first benchmark read, are those of the trace without the
    program's host spans."""
    bare = phases.trace.reduce(_without_program_spans(data), 1, hlo)
    assert reduced.window == bare.window == (CALL[0][0], CALL[-1][1])
    assert reduced.busy_s == bare.busy_s
    assert reduced.op_time_s == bare.op_time_s
    assert reduced.kind_time_s == bare.kind_time_s
    assert reduced.kernel_time_s == bare.kernel_time_s
    assert reduced.gaps == bare.gaps
    assert reduced.gaps["call"] == pytest.approx(
        (_total(CALL) - BUSY_NS) * 1e-9, abs=1e-12)
    idle = harness.metric("device.idle_frac")
    assert idle.reduce({"reduced": reduced}) == idle.reduce(
        {"reduced": bare})


class _Cell:
    def __init__(self, hlo):
        self.text = hlo

    def hlo_text(self):
        return self.text


def _metric(name, hlo, reduced, rounds=9):
    return harness.metric(name).reduce(
        {"reduced": reduced, "cell": _Cell(hlo), "rounds": rounds})


def test_phase_metrics_per_round(hlo, reduced):
    assert _metric("local_train.ms_per_round", hlo, reduced) == (
        pytest.approx(1e-6 * LOCAL_NS / 9, rel=1e-12))
    for name in ("gather.ms_per_round", "psm.ms_per_round",
                 "noise.ms_per_round", "uplink.ms_per_round",
                 "evaluation.ms_per_round"):
        assert _metric(name, hlo, reduced) is None


def test_phase_metrics_read_nothing_without_scopes(phases, reduced, hlo):
    """A program without the scopes (the parent of this change) gives no
    phase metric and raises nothing."""
    bare = phases._OP_NAME.sub("metadata={}", hlo)
    assert set(phases.hlo_scopes(bare).values()) == {"unscoped"}
    assert _metric("local_train.ms_per_round", bare, reduced) is None
