"""The routed experts' share of their roofline: the least time the chip
could take for the three grouped products' forward and both gradients
(roofline/moe_experts.py, from shapes alone: no padding rows, nothing
recomputed) over ``moe_experts_ms``, the device time of everything under
the ``layerNN.experts`` scopes. None where the program has no such scope."""


def reduce(ctx):
    config = ctx["config"]
    if config["family"] != "moe_trunk":
        return None
    experts_ms = ctx["registry"].module("reducers", "moe_experts_ms").reduce(ctx)
    if not experts_ms:
        return None
    roofline = ctx["registry"].module("roofline", "moe_experts")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"moe_expert_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) "
          f"over {experts_ms:.3f} ms under the experts scopes a step")
    return 100.0 * 1e3 * least["least_s"] / experts_ms
