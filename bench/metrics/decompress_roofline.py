"""Least HBM time of one decompress call (``roofline.least_bytes`` at the
chip's peak) over the device busy time per decompress call."""


def read(ctx):
    return ctx.direction_roofline("decompress")
