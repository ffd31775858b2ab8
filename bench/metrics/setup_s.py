"""Seconds from the start of the process to the start of the window:
JAX start-up, device-side fields, programs compiled or loaded, warm-up."""


def read(ctx):
    return ctx.setup_s
