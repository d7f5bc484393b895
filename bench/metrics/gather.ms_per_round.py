"""Device time of the batch gather (``fed.gather``) per round, on the
busiest chip (see metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "batch gather"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "gather")
