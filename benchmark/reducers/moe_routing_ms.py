"""Device ms a traced step in what surrounds the routed experts: the
scopes ``layerNN.router`` (norm, float32 logits, softmax, top-k),
``layerNN.dispatch`` (sort by expert, group sizes, the row gather) and
``layerNN.combine`` (rows back in token order, the weighted sum), forward
and ``transpose(...)`` paths both: pure overhead round the products.
None where the program has no such scope."""


def reduce(ctx):
    experts = ctx["registry"].module("reducers", "moe_experts_ms")
    return experts.part_ms(ctx, ("router", "dispatch", "combine"))
