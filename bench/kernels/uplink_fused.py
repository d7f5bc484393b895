"""FLOPs and HBM bytes of one ``uplink_fused`` call (kernels/mask_uplink).

One call per round takes the K clients' trained updates as one
``(K, P)`` stack, padded by the wrapper to row blocks of 8 clients and
column blocks of 4,096 parameters (``BLOCK_R``, ``BLOCK_C``).  It is the
final uplink draw (no PM gate, no masked-update output), so per padded
element it reads u, the noise and one uniform (f32, 12 bytes).  Per row
block and column block it reads that block's 8 client weights (f32).  It
writes the packed words (int32 view of the uint32 wire, one per 32
elements) and, once per column block, the (1, 4096) count (int32) and
weighted-sum (f32) accumulators, which stay in VMEM across the row
blocks.  Bit packing is int32 shifts and adds (pack_tile); about 12 VPU
operations per element.
"""
BLOCK_R = 8
BLOCK_C = 4096
WORD = 32
FLOPS_PER_ELEMENT = 12


def padded_shape(clients: int, params: int):
    rows = -(-clients // BLOCK_R) * BLOCK_R
    cols = -(-params // BLOCK_C) * BLOCK_C
    return rows, cols


def call_cost(clients: int, params: int):
    """``(flops, hbm_bytes)`` of the call on a (clients, params) stack."""
    rows, cols = padded_shape(clients, params)
    elems = rows * cols
    reads = 3 * 4 * elems + 4 * rows * (cols // BLOCK_C)
    writes = 4 * rows * (cols // WORD) + 2 * 4 * cols
    return FLOPS_PER_ELEMENT * elems, reads + writes


def round_cost(leaf_sizes, clients: int, local_steps: int):
    """``(calls, flops, hbm_bytes)`` of one round: one call."""
    f, b = call_cost(clients, sum(leaf_sizes))
    return 1, f, b
