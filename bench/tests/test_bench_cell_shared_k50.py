"""Cell ``cnn4-cifar10.fedmrn-shared-k50`` on the CPU at a small size: a
sound run of ``bench/run.py`` is correct; with the timed path broken
underneath it is not; the control (the reference in float8 in the
program's place) and the faults planted in the reference fail a compared
number."""
import pytest

from benchutil import (PROGRAM_FAULTS, control_readings, fails_a_limit,
                       run_cell, small_root)

CELL = "cnn4-cifar10.fedmrn-shared-k50"


def test_sound_run_is_correct(tmp_path, monkeypatch):
    rc, res = run_cell(monkeypatch, small_root(tmp_path, CELL), CELL)
    assert rc == 0
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["half_batch", "unchanged_state"])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    PROGRAM_FAULTS[fault](monkeypatch, "fedmrn")
    rc, res = run_cell(monkeypatch, small_root(tmp_path, CELL), CELL)
    assert rc == 0
    assert res["correct"] is False, res["checks"]


def test_control_and_faults_fail_a_limit(tmp_path, monkeypatch):
    limits, res = control_readings(monkeypatch, tmp_path, CELL,
                                   ("half_batch", "unchanged"))
    for r in res["sound"]:
        assert not fails_a_limit(r["numbers"], limits), r["numbers"]
    for r in res["control"]:
        assert fails_a_limit(r["numbers"], limits), r["numbers"]
    for fault in res["faults"]:
        for r in res["faults"][fault]:
            assert fails_a_limit(r["numbers"], limits), (fault, r["numbers"])
