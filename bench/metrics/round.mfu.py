"""The whole round program's share of the chips' bf16 peak: the model
FLOPs of the traced rounds (forward and backward of every local step,
3x the forward FLOPs the configuration counts from its shapes, and the
forward of every evaluation) over window x chips x peak."""
UNIT = "%"
LAYER = "round program"
MOVES = "rounds_per_s"


def reduce(m):
    r = m["reduced"]
    if r.window_s <= 0 or m["rounds"] <= 0:
        return None
    peak = m["chips"] * m["peaks"]["bf16_flops_per_s"]
    return 100.0 * m["cell"].flops(m["rounds"]) / (r.window_s * peak)
