"""HBM the compressor needs beyond its input and output: the larger
temp size of the compiled compress and decompress programs (the TPU
compiler's ``memory_analysis()``), in GiB."""


def read(ctx):
    return ctx.workspace_bytes / 2**30
