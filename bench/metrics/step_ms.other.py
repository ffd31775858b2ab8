"""Device milliseconds a training step, on the first chip, of everything
else: forward, backward, optimizer, in-pod collectives, and the hop's mean
and residual (``bench/steptrace.py``)."""


def read(ctx):
    return ctx.step_ms("other")
