"""Device milliseconds a training step, on the first chip, of the collectives
across pods: the containers' all-gathers (``bench/steptrace.py``)."""


def read(ctx):
    return ctx.step_ms("gather")
