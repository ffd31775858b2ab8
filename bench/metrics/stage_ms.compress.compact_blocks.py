"""Device milliseconds per compress call in the ``fz.stage.compact_blocks`` scope
(``bench.stages``): compaction: the blocks' prefix sum, index scatter and payload gather."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "compress", "compact_blocks")
