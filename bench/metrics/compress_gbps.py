"""Source GB/s compressed: source bytes of every compress call in the
window over all the time charged to compress."""


def read(ctx):
    return ctx.window.source_bytes["compress"] / ctx.window.charged_s["compress"] / 1e9
