"""Device time of the round program's phases per traced round, on the
busiest chip.  Each instruction belongs to the innermost ``fed.<phase>``
name scope the program gave it (``bench/phases.py`` reads them from the
compiled HLO's metadata); the times are those ``trace.py`` gives the
non-control operations.  A phase with no time (a program without the
scopes, or a cell without that phase) reads nothing."""
import sys
from collections import defaultdict

import harness


def ms_per_round(m, *phases):
    per_chip = phase_times(m)
    t = max(sum(c.get(p, 0.0) for p in phases) for c in per_chip)
    if t <= 0 or m["rounds"] <= 0:
        return None
    return 1e3 * t / m["rounds"]


def phase_times(m):
    """Per chip, phase -> device s: computed once per traced run, kept in
    ``m``, and printed with each phase's share of the chip's busy time
    and its convolution time."""
    if "phase_time_s" not in m:
        phases = harness.load_module(harness.BENCH / "phases.py")
        r = m["reduced"]
        scopes = phases.hlo_scopes(m["cell"].hlo_text())
        m["phase_time_s"] = phases.phase_time_s(r, scopes)
        _report(phases.phase_kind_time_s(r, scopes), r.busy_s,
                max(m["rounds"], 1), phases.UNSCOPED)
    return m["phase_time_s"]


def _report(kinds, busy_s, rounds, unscoped):
    chip = max(range(len(kinds)), key=lambda i: busy_s[i])
    total, conv = defaultdict(float), defaultdict(float)
    for (phase, kind), s in kinds[chip].items():
        total[phase] += s
        if kind == "convolution":
            conv[phase] += s
    ops = sum(total.values())
    scoped = 1 - total.get(unscoped, 0.0) / ops if ops else 0.0
    parts = [f"{k} {1e3 * v / rounds!r} ({100 * v / busy_s[chip]:.2f}%; "
             f"{1e3 * conv[k] / rounds!r})"
             for k, v in sorted(total.items(), key=lambda kv: -kv[1])]
    print(f"phases on chip {chip}, ms/round (share of busy; convolutions): "
          + ", ".join(parts) + f"; scoped {100 * scoped:.2f}% of op time",
          file=sys.stderr)
