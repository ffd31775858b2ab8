"""Share of the time charged to decompress in which no operation ran on the
device, from the traced window."""


def read(ctx):
    return ctx.idle_share("decompress")
