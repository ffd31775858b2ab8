"""Share of its HBM roofline of the ``bitshuffle_flag`` Pallas kernel
(bit transpose and zero-block flags)."""


def read(ctx):
    return ctx.kernel_roofline("bitshuffle_flag")
