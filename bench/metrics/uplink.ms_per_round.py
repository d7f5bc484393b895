"""Device time of the uplink, aggregation and server apply
(``fed.uplink`` and ``fed.server``) per round, on the busiest chip (see
metrics/_phase.py)."""
import harness

UNIT = "ms/round"
LAYER = "uplink and server"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_phase").ms_per_round(m, "uplink", "server")
