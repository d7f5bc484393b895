"""``uplink_fused``'s share of its roofline (see metrics/_roofline.py)."""
import harness

UNIT = "%"
LAYER = "mask uplink"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_roofline").share(m, "uplink_fused")
