"""Device ms a traced step in the trunk's attention: the scopes
``layerNN.attention`` (norm, the four projections, qk-norm, RoPE, the
64 x 64 softmax), forward and ``transpose(...)`` paths both. None where
the program has no such scope."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("attention",))
