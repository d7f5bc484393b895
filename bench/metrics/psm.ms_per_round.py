"""Device time of PSM masking (``fed.psm``) per round, on the busiest
chip: the masked update and its mix with the global weights, forward and
backward, ``psm_fused`` included (see metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "PSM masking"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "psm")
