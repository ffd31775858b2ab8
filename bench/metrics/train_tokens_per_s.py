"""Tokens trained per second: the tokens of every timed step over the summed
wall time of those steps, each from before its batch is made to the host
holding its loss."""


def read(ctx):
    return ctx.tokens_per_s()
