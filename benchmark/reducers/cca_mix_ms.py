"""Device ms a traced step in the convolutional mixing of the trunk's
compressed attention: the scopes ``layerNN.cca`` (the two convolutions
over queries and keys, the q-k mean, the move of the shifted value
halves), forward and ``transpose(...)`` paths both. With
``trunk_attention_ms`` (the input norm, the joined projections, the core
and the output projection) it adds up to the attention branch. None where
the program has no such scope (a trunk without the mix, or a parent
without the fifth block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("cca",))
