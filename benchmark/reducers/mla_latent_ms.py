"""Device ms a traced step on the latent key-value path of the trunk's
attention: the scopes ``layerNN.latent`` (the down-projection to the
latent and the RoPE key, the latent's RMSNorm, the up-projection to every
head's key and value, and their gradients), forward and
``transpose(...)`` paths both. With ``trunk_attention_ms`` (the input
norm, the query projection, the core and the output projection) it adds
up to the attention branch. None where the program has no such scope (a
trunk without a latent, or a parent without the third block)."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("latent",))
