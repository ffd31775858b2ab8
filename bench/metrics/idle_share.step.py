"""Share of the ``bench.step`` spans in which no operation ran on the
device, from the traced window, the mean of the cell's chips."""


def read(ctx):
    return ctx.idle_share()
