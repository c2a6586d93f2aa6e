"""The state-space scan's share of its roofline: the least time the chip
could take for a step's recurrence over every board, head and mixer,
forward and gradient (roofline/ssd_scan.py, from shapes and the
configuration's stated precision alone, whatever implements the core)
over the device time a traced step of everything under the scopes
``layerNN.scan`` of ``models/trunk.py``, forward and ``transpose(...)``
paths both: the kernel pair ``board_scan`` / ``board_scan_grad`` and
what XLA does to hand them their operands, or whatever else computes the
core under that scope. None without a trace, for a configuration without
a layer pattern, or where the program has no such scope."""


def reduce(ctx):
    config = ctx["config"]
    if "pattern" not in config["model"]:
        return None
    scan_ms = ctx["registry"].module("reducers", "moe_experts_ms").part_ms(ctx, ("scan",))
    if not scan_ms:
        return None
    roofline = ctx["registry"].module("roofline", "ssd_scan")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"ssm_scan_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) for {roofline.scan_layers(config['model'])} mixers "
          f"over {scan_ms:.3f} ms under the scan scopes a step")
    return 100.0 * 1e3 * least["least_s"] / scan_ms
