"""``psm_fused``'s share of its roofline (see metrics/_roofline.py)."""
import harness

UNIT = "%"
LAYER = "PSM kernel"
MOVES = "rounds_per_s"


def reduce(m):
    return harness.metric("_roofline").share(m, "psm_fused")
