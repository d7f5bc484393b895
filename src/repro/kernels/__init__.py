"""Pallas TPU kernels (compiled by Mosaic on TPU; interpreted on CPU).

psm_mask     fused PSM masking chain (the paper's hot elementwise path)
bitpack      1-bit mask wire-format pack/unpack
rwkv6_scan   RWKV6 wkv linear-attention recurrence (chunked, VMEM state)
mask_uplink  whole-uplink fusion: PSM sample → bitpack → popcount /
             weighted-sum partials in one pass (+ the server-side
             counts→update apply kernel)

See README.md in this directory for the family inventory and the
dispatch/fallback rules.
"""
