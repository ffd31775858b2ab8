"""Share of its HBM roofline of the bit-unshuffle Pallas kernel, which
its wrapper names ``bitunshuffle_tiles``."""


def read(ctx):
    return ctx.kernel_roofline("bitunshuffle_tiles")
