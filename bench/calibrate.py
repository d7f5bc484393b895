"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --others 3
                               --out <cell>.calibrate.json

In one process, on the chip the cell asks for:

- sound runs: for each seed, the program's checked calls (the first
  call and, with the mix's ``check_calls``, the calls after it with
  seeds ``seed + 1, ...``) from that seed's weights, against the plain
  reference; the lower reading of a number is its largest here;
- the control: the reference computed in float8 (e4m3) in place of the
  program, against the reference, on the first ``--others`` seeds;
- the faults, planted in the reference put in the program's place, on
  the same seeds: the weights left unchanged by every round
  (``unchanged``) and half of every batch left out (``half_batch``).

Every number of ``check.numbers`` is written, per seed and per side, to
``--out``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import check  # noqa: E402
import harness  # noqa: E402


def as_program(first: dict, out: dict) -> dict:
    """A reference's output in the place of the program's first call."""
    prog = {**first, "w": out["w"], "loss": out["loss"]}
    if "acc" in out:
        prog["acc"] = out["acc"]
    if "calls" in first:
        prog["calls"] = [(seed, loss0) for (seed, _), loss0 in
                         zip(first["calls"], out["calls_loss0"])]
    return prog


def detail(prog: dict, ref: dict) -> dict:
    """Both sides' losses, accuracies and per-leaf change norms."""
    out = {"loss": [prog["loss"], ref["loss"]]}
    if prog.get("acc"):
        out["acc"] = [list(prog["acc"].values()), list(ref["acc"].values())]
    if "calls" in prog:
        out["loss0_calls"] = [[p for _, p in prog["calls"]],
                              ref["calls_loss0"]]
    out["dw"] = {str(t): [check.leaf_norms(w, prog["w0"]).tolist(),
                          check.leaf_norms(ref["w"][t], prog["w0"]).tolist()]
                 for t, w in prog["w"].items()}
    out["grad_norms"] = ref["grad_norms"].tolist()
    return out


def readings(cell, seeds, others, faults, log=print, control_run=True):
    fam, cfg = cell.fam, cell.cfg
    sound, control, fault_out = [], [], {f: [] for f in faults}
    for i, seed in enumerate(seeds):
        s = harness.program_seed(seed)
        t0 = time.time()
        params = fam.init_params(cfg, s)
        first = cell.first_call(seed=s, params=params)
        if "check_calls" in cell.tr:
            first["calls"] = [
                (s + j, cell.first_call(seed=s + j, params=params)["loss"][0])
                for j in range(1, cell.tr["check_calls"])]
        t1 = time.time()
        ref = cell.reference(first)
        t2 = time.time()
        nums = cell.compare(first, ref)
        sound.append({"seed": seed, "numbers": nums, "first_s": t1 - t0,
                      "reference_s": t2 - t1, **detail(first, ref)})
        log(f"sound seed {seed}: {nums}")
        if i >= others:
            continue
        if control_run:
            ctrl = cell.reference(first, quant=fam.fp8)
            nums = cell.compare(as_program(first, ctrl), ref)
            control.append({"seed": seed, "numbers": nums,
                            **detail(as_program(first, ctrl), ref)})
            log(f"control seed {seed}: {nums}")
        for f in faults:
            out = cell.reference(first, fault=f)
            nums = cell.compare(as_program(first, out), ref)
            fault_out[f].append({"seed": seed, "numbers": nums,
                                 **detail(as_program(first, out), ref)})
            log(f"fault {f} seed {seed}: {nums}")
    return {"sound": sound, "control": control, "faults": fault_out}


def summary(res: dict) -> dict:
    """Per number: the lower reading (largest sound), the control's
    smallest, and each fault's smallest."""
    names = sorted(res["sound"][0]["numbers"])
    out = {}
    for n in names:
        row = {"lower": max(r["numbers"][n] for r in res["sound"])}
        if res["control"]:
            row["control_min"] = min(r["numbers"][n] for r in res["control"])
        for f, rs in res["faults"].items():
            if rs:
                row[f"{f}_min"] = min(r["numbers"][n] for r in rs)
        out[n] = row
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--others", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    ctx = harness.workload(args.workload)
    jax = harness.setup_jax(ctx["chips"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    t0 = time.time()
    cell = harness.engine(ctx["traffic"]).Cell(ctx, seeds[0])
    res = readings(cell, seeds, args.others, ["unchanged", "half_batch"],
                   log=lambda s: print(s, file=sys.stderr, flush=True))
    res["summary"] = summary(res)
    res["device"] = harness.device_info(jax, ctx["chips"])
    res["wall_s"] = time.time() - t0
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
