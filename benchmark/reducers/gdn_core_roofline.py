"""The gated delta rule's share of its roofline in a Gated DeltaNet mixer:
the least time the chip could take for a step's recurrence over every
board, value head and GDN mixer, forward and gradient (roofline/gdn_core.py,
from shapes and the configuration's stated precision alone, whatever
implements the core: q and k once a KEY head, one decay a value head and
token) over the device time a traced step of everything under the scopes
``layerNN.delta`` of ``models/trunk.py``, forward and ``transpose(...)``
paths both: the kernel pair ``board_delta`` / ``board_delta_grad`` in its
second form and what XLA does to hand them their operands, or whatever else
computes the core under that scope. None without a trace, for a
configuration without such a mixer, or where the program has no such
scope."""


def reduce(ctx):
    config = ctx["config"]
    if "gdn" not in config["model"].get("mixers", ()):
        return None
    core_ms = ctx["registry"].module("reducers", "moe_experts_ms").part_ms(ctx, ("delta",))
    if not core_ms:
        return None
    roofline = ctx["registry"].module("roofline", "gdn_core")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"gdn_core_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) for {roofline.gdn_layers(config['model'])} mixers "
          f"over {core_ms:.3f} ms under the delta scopes a step")
    return 100.0 * 1e3 * least["least_s"] / core_ms
