"""Device milliseconds per decompress call in the ``fz.stage.dequantize`` scope
(``bench.stages``): the outlier scatter, inverse Lorenzo prefix sums and scaling."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "decompress", "dequantize")
