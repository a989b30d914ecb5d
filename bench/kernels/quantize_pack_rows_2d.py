"""``quantize_pack_rows_2d``: dither -> quantize -> bit-pack.

Operands: the (N, 2) per-device scalars, the (N, d) gradients and the
dither of the same shape, read in place; result: (N, W) uint32 words,
K = 32 / code_bits codes a word, W = d / K padded to whole column blocks.
Per gradient element the quantizer (``dithered_quant.quantize_codes``)
adds m, divides by the step, floors, subtracts the floor, compares the
dither, adds the carry and clips twice (8 operations); packing shifts and
ors the code into its word (2). Gradients and dither are read and the
words written once each.
"""
from bench.trace import hbm_bytes


def cost(operands, results) -> tuple:
    grads = operands[1]
    flops = 10 * grads.size
    return flops, hbm_bytes(list(operands) + list(results))
