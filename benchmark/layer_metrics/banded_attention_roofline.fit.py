"""kernels: the attention layers' share of the chip's peak, in percent: the
FLOP the attention of every layer needs in the traced steps (9 product
passes over the pairs inside each layer's causal band, from the
configuration's sizes: `harness/kernel_costs.py`) over the time under the
scopes `attn.sliding` and `attn.full` times the chip's peak bf16 FLOP/s.
Bound by compute (at Dh 128 a pair is 2,304 FLOP for a few bytes). It
divides by the time under the layers' scopes and not by the time in calls
of one name, so a relayout beside the kernel or a tile of masked pairs
lowers it, and it cannot pass 100%."""

SCOPES = ("attn.sliding", "attn.full")


def read(context):
    import jax

    from benchmark.harness import device, kernel_costs

    dev = jax.devices()[0]
    if dev.device_kind not in device.CHIP_PEAKS:
        return None  # a rehearsal off-chip has no peak
    found = kernel_costs.scoped_seconds_and_steps(context, SCOPES)
    if not found or not found[0]:
        return None
    seconds, steps = found
    flops = steps * kernel_costs.attention_step_flops(context["cell"].sizes)
    return 100.0 * flops / (seconds * device.peak_flops(dev.device_kind))
