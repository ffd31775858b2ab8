"""Device milliseconds a training step, on the first chip, inside the
compressor's decompress program (its ``fz.stage.*`` scopes, or
``_decompress_jit`` on the op's path): decoding every pod's container
(``bench/steptrace.py``)."""


def read(ctx):
    return ctx.step_ms("fz_decompress")
