"""Share of its HBM roofline of the ``lorenzo_quant`` Pallas kernel
(pre-quantization, Lorenzo, codes; residuals in strict mode)."""


def read(ctx):
    return ctx.kernel_roofline("lorenzo_quant")
