"""``quantize_pack_rows_2d``'s share of its roofline: the time the chip's peaks need
for its launches' FLOPs and HBM bytes (``bench/kernels/quantize_pack_rows_2d.py``),
over the device time of those launches in the traced window."""


def read(ctx):
    return ctx.roofline_share("quantize_pack_rows_2d")
