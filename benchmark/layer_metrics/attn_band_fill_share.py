"""kernels: pairs inside an attention layer's causal band over pairs in the
tiles its kernel visits, worst layer, in percent (the gauge
`dl4j_attn_band_fill_share`: static per layer, sequence length and block
choice; 100 would be no wasted tile). None where the program has no such
gauge, or where a layer reads 0: its XLA body ran, which visits no tiles."""


def read(context):
    from deeplearning4j_tpu import observability as obs

    family = obs.metrics.get_family("dl4j_attn_band_fill_share")
    values = [c.get() for c in family.children()] if family else []
    return 100.0 * min(values) if values and min(values) > 0 else None
