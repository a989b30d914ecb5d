"""``quantize_pack_rows_2d``: dither -> quantize -> bit-pack.

Operands: the (N, 1, 2) per-device scalars, the padded (N R, 128) gradient
rows and the dither rows of the same shape; result: (N R / K, 128) uint32
words, K = 32 / code_bits codes a word. Per gradient element the
quantizer (``dithered_quant.quantize_codes``) adds m, divides by the step,
floors, subtracts the floor, compares the dither, adds the carry and clips
twice (8 operations); packing shifts and ors the code into its word (2).
Gradient and dither rows are read and the words written once each.
"""
from bench.trace import hbm_bytes


def cost(operands, results) -> tuple:
    grads = operands[1]
    flops = 10 * grads.size
    return flops, hbm_bytes(list(operands) + list(results))
