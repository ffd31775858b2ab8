"""Device milliseconds per compress call in the ``fz.stage.collect_outliers`` scope
(``bench.stages``): the exact-outlier channel: the residuals' ``nonzero`` and gather (strict mode)."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "compress", "collect_outliers")
