"""Device milliseconds per compress call of every operation that is not a
Pallas kernel (the XLA epilogue: compaction, outliers, decode, inverse Lorenzo)."""


def read(ctx):
    return ctx.xla_ms("compress")
