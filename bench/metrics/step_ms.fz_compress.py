"""Device milliseconds a training step, on the first chip, inside the
compressor's compress program (its ``fz.stage.*`` scopes, or
``_compress_jit`` on the op's path): bound, quantization, shuffle and
compaction of each pod's gradient (``bench/steptrace.py``)."""


def read(ctx):
    return ctx.step_ms("fz_compress")
