"""Device milliseconds per compress call in the ``fz.stage.quantize`` scope
(``bench.stages``): the ``lorenzo_quant`` kernel and its glue, outside ``collect_outliers``."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "compress", "quantize")
