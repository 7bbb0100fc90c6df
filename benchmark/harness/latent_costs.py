"""Operations the latent attention of a configuration needs, computed from
its sizes alone: the numerator of `latent_attention_roofline.fit`. Nothing
here looks at a kernel's tiles, blocks or name.

The nine product passes of `kernel_costs` over the (query, key) pairs inside
the causal band, at this attention's two widths: the score is a sum of two
products, q_n k_n^T over `qk_nope_head_dim` and q_r k_r^T over
`qk_rope_head_dim`, and the values are `v_head_dim` wide. Five passes run at
the score's width (forward q k^T; dq's q k^T and ds k; dk/dv's k q^T and
ds^T q), four at the values' (forward p v; dq's do v^T; dk/dv's p^T do and
v do^T): 5 * 2 * (128 + 64) + 4 * 2 * 128 = 2,944 FLOP a pair and head at
the published widths, where operands padded to one width of 256 would spend
9 * 2 * 256 = 4,608.
"""

from __future__ import annotations

from benchmark.harness import kernel_costs

SCORE_PASSES, VALUE_PASSES = 5, 4
assert SCORE_PASSES + VALUE_PASSES == kernel_costs.ATTENTION_TRAIN_PASSES


def pair_flops(nope: int, rope: int, v: int) -> int:
    """FLOP one (query, key) pair of one head costs, forward and backward."""
    return SCORE_PASSES * 2 * (nope + rope) + VALUE_PASSES * 2 * v


def attention_step_flops(sizes: dict) -> int:
    """FLOP of the latent attention of all layers of one train step of a
    configuration whose file gives `qk_nope_head_dim`, `qk_rope_head_dim`,
    `v_head_dim`, `num_attention_heads`, `num_hidden_layers`, `seq_len` and
    `batch_per_chip`: every layer is causal over the whole sequence."""
    return (int(sizes["batch_per_chip"]) * int(sizes["num_hidden_layers"])
            * int(sizes["num_attention_heads"])
            * kernel_costs.band_pairs(int(sizes["seq_len"]))
            * pair_flops(int(sizes["qk_nope_head_dim"]),
                         int(sizes["qk_rope_head_dim"]),
                         int(sizes["v_head_dim"])))


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
