"""Compression ratio: source bytes of every compress call in the window
over the used bytes (``used_bytes()``, outliers included) of its containers."""


def read(ctx):
    return ctx.window.source_bytes["compress"] / ctx.window.used_bytes
