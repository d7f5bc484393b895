"""Device time of the convolution operations per round, on the busiest
chip: local training's forward and backward convolutions (and the
evaluation's forward ones in rounds that evaluate)."""
UNIT = "ms/round"
LAYER = "local training"
MOVES = "rounds_per_s"


def reduce(m):
    r = m["reduced"]
    t = max(k.get("convolution", 0.0) for k in r.kind_time_s)
    if t <= 0 or m["rounds"] <= 0:
        return None
    return 1e3 * t / m["rounds"]
