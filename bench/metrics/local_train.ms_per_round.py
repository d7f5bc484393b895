"""Device time of local training (``fed.local_train``) per round, on
the busiest chip: forward, backward and update of every local step, with
neither the PSM masking nor the evaluation (see metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "local training"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "local_train")
