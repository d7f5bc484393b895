"""A Pallas kernel's share of its roofline: the least time the chip
could take for the traced calls, the larger of FLOPs over the bf16 peak
and HBM bytes over the HBM bandwidth (counted from the shapes by
``kernels/<kernel>.py``), over the kernel's summed device time."""
import sys

import harness


def share(m, kernel: str):
    r = m["reduced"]
    measured = r.op_time(kernel)
    if measured <= 0 or m["rounds"] <= 0:
        return None
    cell = m["cell"]
    calls, flops, nbytes = harness.kernel(kernel).round_cost(
        m["leaf_sizes"], cell.tr["clients_per_round"],
        cell.tr["local_steps"])
    t_flops = flops * m["rounds"] / m["peaks"]["bf16_flops_per_s"]
    t_bytes = nbytes * m["rounds"] / m["peaks"]["hbm_bytes_per_s"]
    bound = "hbm" if t_bytes >= t_flops else "flops"
    print(f"{kernel}.roofline: bound={bound} calls expected "
          f"{calls * m['rounds']} traced {r.op_calls(kernel)} bytes/round "
          f"{nbytes} flops/round {flops} device {measured!r} s",
          file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / measured
