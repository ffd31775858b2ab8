"""Model FLOPs utilization of the training step: ``train_tokens_per_s``
times the model FLOPs a token (``bench/flops.py``, the PaLM convention)
over the bf16 peak of all the cell's chips (``bench/peaks.json``)."""


def read(ctx):
    return ctx.mfu()
