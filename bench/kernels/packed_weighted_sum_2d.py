"""``packed_weighted_sum_2d``: unpack -> dequantize -> weighted sum.

Operands: the (N, 1, 3) per-device scalars and the (N R / K, 128) packed
words; result: the (R, 128) f32 accumulator. Per code (N x the result's size)
the kernel shifts and masks it out of its word (2), converts it (1),
dequantizes ``-m + step q`` (2) and accumulates ``acc + w x`` (2): 7
operations. The words are read once; the accumulator block stays resident
across the device axis and is written once.
"""
from bench.trace import hbm_bytes


def cost(operands, results) -> tuple:
    n_codes = operands[0].shape[0] * results[0].size
    return 7 * n_codes, hbm_bytes(list(operands) + list(results))
