"""Device ms a traced step in the trunk's Gated DeltaNet mixers: the scopes
``layerNN.gdn`` (norm, the joined q, k, v, z projection and the b, a
projection, the convolution with its silu, beta, softplus and the decay,
the gated head norm, out-projection, residual) and ``layerNN.delta`` (the
delta rule's core beside it), forward and ``transpose(...)`` paths both.
None where the program has no ``layerNN.gdn`` scope (a trunk without such a
mixer, or a parent without the seventh block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    if not experts.part_ms(ctx, ("gdn",)):
        return None
    return experts.part_ms(ctx, ("gdn", "delta"))
