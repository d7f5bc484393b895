"""A new configuration, traffic mix and per-layer metric are found by
name: dropping their files into a copy of the benchmark and adding one
``workloads`` entry (and the metric's ``per_layer`` entry) is enough;
no file that was there changes."""
import hashlib
import json
import shutil

import harness
from benchutil import BENCH, ROOT


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root)

    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "cnn4-fmnist.json").read_text())
    cfg["channels"] = [32, 32, 64, 64]
    (bench / "configs" / "cnn4-narrow.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "fedmrn-k10.json").read_text())
    tr["clients_per_round"] = 20
    (bench / "traffic" / "fedmrn-k20.json").write_text(json.dumps(tr))
    (bench / "limits" / "cnn4-narrow.fedmrn-k20.json").write_text(
        json.dumps({"loss_r0": 0.5}))
    (bench / "metrics" / "eval.ms_per_round.py").write_text(
        'UNIT = "ms/round"\nLAYER = "evaluation"\n'
        'MOVES = "rounds_per_s"\n\n\ndef reduce(m):\n    return 1.5\n')
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "cnn4-narrow", "source": "x",
                            "file": "bench/configs/cnn4-narrow.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "cnn4-narrow.fedmrn-k20",
                              "config": "cnn4-narrow",
                              "traffic": "fedmrn-k20", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({
        "name": "eval.ms_per_round", "unit": "ms/round", "better": "lower",
        "source": "device_trace", "layer": "evaluation",
        "moves": "rounds_per_s", "workloads": ["cnn4-narrow.fedmrn-k20"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", bench)
    ctx = harness.workload("cnn4-narrow.fedmrn-k20")
    assert ctx["config"]["channels"] == [32, 32, 64, 64]
    assert ctx["traffic"]["clients_per_round"] == 20
    assert ctx["limits"] == {"loss_r0": 0.5}
    names = [m["name"] for m in ctx["per_layer"]]
    assert "eval.ms_per_round" in names and "round.mfu" in names
    # listed for other cells only
    assert "psm_fused.roofline" not in names
    assert harness.metric("eval.ms_per_round").reduce({}) == 1.5
    assert harness.family(ctx["config"]).num_params(ctx["config"]) > 0
    assert harness.engine(ctx["traffic"]).Cell is not None
    after = _digests(root)
    changed = [p for p, d in before.items() if after.get(p) != d]
    assert [str(p) for p in changed] == ["BENCHMARK.json"]
