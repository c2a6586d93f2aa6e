"""The convolutional mixing's share of its roofline: the least time the
chip could take for a step's mix over every board and layer, forward and
gradient (roofline/cca_mix.py, from shapes and the configuration's stated
precision alone, whatever implements the mix) over the device time a
traced step of everything under the scopes ``layerNN.cca`` of
``models/trunk.py``, forward and ``transpose(...)`` paths both: the
kernel pair ``cca_mix`` / ``cca_mix_grad``, the move of the shifted value
halves beside them and what XLA does to hand them their operands, or
whatever else computes the mix under that scope. None without a trace,
for a configuration without the mix, or where the program has no such
scope."""


def reduce(ctx):
    config = ctx["config"]
    if "cca_time0" not in config["model"]:
        return None
    mix_ms = ctx["registry"].module("reducers", "moe_experts_ms").part_ms(ctx, ("cca",))
    if not mix_ms:
        return None
    roofline = ctx["registry"].module("roofline", "cca_mix")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"cca_mix_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) for {config['model']['num_hidden_layers']} layers "
          f"over {mix_ms:.3f} ms under the cca scopes a step")
    return 100.0 * 1e3 * least["least_s"] / mix_ms
