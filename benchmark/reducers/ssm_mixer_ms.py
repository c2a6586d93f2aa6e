"""Device ms a traced step in the trunk's Mamba-2 mixers: the scopes
``layerNN.mamba`` (norm, in-projection, convolution, softplus, the gated
grouped norm, out-projection, residual) and ``layerNN.scan`` (the scan's
core beside it), forward and ``transpose(...)`` paths both. None where
the program has no such scope (a trunk without a layer pattern, or a
parent without the fourth block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("mamba", "scan"))
