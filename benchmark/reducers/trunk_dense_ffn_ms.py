"""Device ms a traced step in the trunk's feed-forwards that every token
passes: the scopes ``layerNN.shared`` (the shared expert beside the
routed ones) and ``layerNN.dense`` (a leading dense layer's norm,
feed-forward, post-norm and residual), forward and ``transpose(...)``
paths both. None where the program has no such scope (the first trunk,
or a parent without the second block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("shared", "dense"))
