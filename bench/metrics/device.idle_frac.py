"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses: 1 - busy / window, where busy is
the union of the device's operation intervals.  The host spans that the
idle time falls in are the run's ``breakdown.idle_gaps``."""
UNIT = "%"
LAYER = "device"
MOVES = "rounds_per_s"


def reduce(m):
    r = m["reduced"]
    if r.window_s <= 0:
        return None
    busy = sum(r.busy_s) / len(r.busy_s)
    return 100.0 * (1.0 - busy / r.window_s)
