"""Device milliseconds per decompress call in the ``fz.stage.unshuffle`` scope
(``bench.stages``): the ``bitunshuffle_tiles`` kernel and its glue."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "decompress", "unshuffle")
