"""Device time of noise generation (``fed.noise``) per round, on the
busiest chip: every draw, client or server (see metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "noise generation"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "noise")
