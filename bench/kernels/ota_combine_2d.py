"""``ota_combine_2d``: ghat = g * inv_alpha + z, elementwise.

Operands: the (1, 1) scalar, the gradient block and the pre-scaled noise
block; result: one block of the gradient's shape. A vmapped launch carries
the trials as a leading axis. Two operations (a multiply and an add) per
result element; every operand and the result cross HBM once, unless the
compiler placed them in VMEM.
"""
from bench.trace import hbm_bytes


def cost(operands, results) -> tuple:
    flops = 2 * sum(r.size for r in results)
    return flops, hbm_bytes(list(operands) + list(results))
