"""Least HBM time of one compress call (``roofline.least_bytes`` at the
chip's peak) over the device busy time per compress call."""


def read(ctx):
    return ctx.direction_roofline("compress")
