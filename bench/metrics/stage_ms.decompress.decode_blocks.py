"""Device milliseconds per decompress call in the ``fz.stage.decode_blocks`` scope
(``bench.stages``): flag unpacking, block offsets and the decode gather."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "decompress", "decode_blocks")
