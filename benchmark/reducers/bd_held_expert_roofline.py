"""The ninth trunk's held routed experts' share of their roofline, as
``moe_eighth_held_expert_roofline`` reads the eighth trunk's: the least time
the chip could take for the three grouped products' forward and both
gradients on the rows the 8 held of 128 experts receive under even routing
(roofline/moe_held_experts.py, the accepted count, from shapes alone: no
padding rows, no absent experts' rows, nothing made again) over
``moe_experts_ms``, the device time of everything under the
``layerNN.experts`` scopes, the backward pass's remade forward products
included. That count is of 64 tokens a board; a step of this trunk routes a
clean and a noised copy of every board, 128 tokens, so it is given the
copies the step routes: ``2 x batch`` boards' tokens. None where the program
has no such scope or for another family's configuration."""

COPIES = 2  # of every board, each routed


def reduce(ctx):
    config = ctx["config"]
    if config["family"] != "sdar_trunk":
        return None
    experts_ms = ctx["registry"].module("reducers", "moe_experts_ms").reduce(ctx)
    if not experts_ms:
        return None
    roofline = ctx["registry"].module("roofline", "moe_held_experts")
    routed = COPIES * ctx["batch"]
    least = roofline.least_seconds(config["model"], routed, ctx["registry"].peaks(ctx["device_kind"]))
    print(f"bd_held_expert_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) for "
          f"{roofline.held_slots(config['model'], routed):.0f} expected held slots a routed layer at 128 tokens a board "
          f"over {experts_ms:.3f} ms under the experts scopes a step")
    return 100.0 * 1e3 * least["least_s"] / experts_ms
