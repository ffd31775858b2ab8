"""Device milliseconds per compress call in the ``fz.stage.shuffle_encode`` scope
(``bench.stages``): padding to tiles and the ``bitshuffle_flag`` kernel, outside ``compact_blocks``."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "compress", "shuffle_encode")
