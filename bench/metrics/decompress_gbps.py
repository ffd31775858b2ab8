"""Source GB/s reconstructed: source bytes of every decompress call in the
window over all the time charged to decompress."""


def read(ctx):
    return ctx.window.source_bytes["decompress"] / ctx.window.charged_s["decompress"] / 1e9
