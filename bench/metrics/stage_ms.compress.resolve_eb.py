"""Device milliseconds per compress call in the ``fz.stage.resolve_eb`` scope
(``bench.stages``): the bound: min, max and abs-max reductions and the snap."""
from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "compress", "resolve_eb")
