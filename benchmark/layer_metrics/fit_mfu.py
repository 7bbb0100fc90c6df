"""kernels: model FLOP/s utilisation of the whole cell, in percent.

samples/s x train FLOPs per sample / (chips x peak bf16 FLOP/s), where the
train FLOPs of a sample are 3 x 2 x the multiply-adds of the forward pass
(forward, and twice that for the backward), counted from the shapes of the
configuration's own forward pass by `harness/flops.py`."""


def read(context):
    import jax

    from benchmark.harness import device, flops

    built, cell = context["built"], context["cell"]
    dev = jax.devices()[0]
    if dev.device_kind not in device.CHIP_PEAKS:
        return None  # a rehearsal off-chip has no peak
    macs = flops.forward_macs(built["forward"], built["net"].params_tree,
                              built["example_input"]())
    achieved = context["rate"] * 3 * 2 * macs
    return 100.0 * achieved / (cell.chips * device.peak_flops(dev.device_kind))
