"""Device milliseconds per decompress call in the ``fz.stage.other`` scope
(``bench.stages``): device time under no stage scope: it shows where the scopes lost coverage."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "decompress", "other")
