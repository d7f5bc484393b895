"""FLOPs and HBM bytes of one ``psm_fused`` call (kernels/psm_mask).

One call masks one parameter leaf for all K clients of a local step (the
round body vmaps the clients, which adds a grid axis).  The wrapper
flattens the leaf to lane tiles ``(ceil(n / 128), 128)``; per padded
element the kernel reads u, n and the two uniforms (f32) and writes the
masked update (f32) and the mask (int8); the progress scalar rides in
SMEM.  About 10 VPU operations per element: divide, two clips, two
compares, min, max and three selects.
"""
LANE = 128
BYTES_PER_ELEMENT = 4 * 4 + 4 + 1
FLOPS_PER_ELEMENT = 10


def padded(n: int) -> int:
    return -(-n // LANE) * LANE


def call_cost(leaf_size: int, clients: int):
    """``(flops, hbm_bytes)`` of the call on one leaf."""
    elems = clients * padded(leaf_size)
    return FLOPS_PER_ELEMENT * elems, BYTES_PER_ELEMENT * elems


def round_cost(leaf_sizes, clients: int, local_steps: int):
    """``(calls, flops, hbm_bytes)`` of one round: one call per leaf per
    local step."""
    calls = flops = nbytes = 0
    for n in leaf_sizes:
        f, b = call_cost(n, clients)
        calls += local_steps
        flops += local_steps * f
        nbytes += local_steps * b
    return calls, flops, nbytes
