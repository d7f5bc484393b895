"""Device time of the evaluation (``fed.eval``) per round, on the busiest
chip: the rounds that evaluate, averaged over every traced round (see
metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "evaluation"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "eval")
