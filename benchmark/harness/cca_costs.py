"""Operations the compressed convolutional attention of a configuration
needs, computed from its sizes alone: the numerator of
`cca_attention_roofline.fit`. Nothing here looks at a kernel's tiles, blocks
or name, nor at the names of the configuration's `layer_types`.

The core of a CCA layer is causal grouped-query attention over the heads as
they come out of the convolutions: `num_attention_heads` query heads of
`head_dim`, every layer over the whole sequence. Its count is
`kernel_costs.attention_train_flops`, the nine product passes over the
(query, key) pairs inside the causal band: 9 * 2 * 128 * 8 FLOP a pair at
the published widths. The convolutions, the q-k mean, the norms and the
rotary step lie outside the core's scope (`cca.conv`) and outside this
count.
"""

from __future__ import annotations

from benchmark.harness import kernel_costs


def attention_step_flops(sizes: dict) -> int:
    """FLOP of the CCA core of all layers of one train step of a
    configuration whose file gives `num_attention_heads`, `head_dim`,
    `num_hidden_layers`, `seq_len` and `batch_per_chip`."""
    return (int(sizes["batch_per_chip"]) * int(sizes["num_hidden_layers"])
            * kernel_costs.attention_train_flops(
                int(sizes["seq_len"]), int(sizes["num_attention_heads"]),
                int(sizes["head_dim"])))


def roofline_percent(context, scopes):
    """The needed FLOP of the traced steps over the time under `scopes`
    times the chip's peak, in percent; None off the chip, without a trace or
    where no operation carries the scopes (a program that has no such
    layer)."""
    import jax

    from benchmark.harness import device

    dev = jax.devices()[0]
    if dev.device_kind not in device.CHIP_PEAKS:
        return None  # a rehearsal off-chip has no peak
    found = kernel_costs.scoped_seconds_and_steps(context, scopes)
    if not found or not found[0]:
        return None
    seconds, steps = found
    flops = steps * attention_step_flops(context["cell"].sizes)
    return 100.0 * flops / (seconds * device.peak_flops(dev.device_kind))
