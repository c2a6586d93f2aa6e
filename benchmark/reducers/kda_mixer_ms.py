"""Device ms a traced step in the trunk's Kimi Delta Attention mixers:
the scopes ``layerNN.kda`` (norm, the joined q, k, v projection, the
convolution with its silu, the two low-rank gates, beta, the gated head
norm, out-projection, residual) and ``layerNN.delta`` (the delta rule's
core beside it), forward and ``transpose(...)`` paths both. None where
the program has no such scope (a trunk without such a mixer, or a parent
without the sixth block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("kda", "delta"))
