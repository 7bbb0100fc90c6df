"""kernels: the share of a step's tokens that the MLP routers send to their
skip choice, which no chip holds and which adds nothing, over all such
layers, in percent, at the last step the score was read (the mean of
`dl4j_moe_skip_share` over the layers: each routes the same number of
tokens). An even router over 16 experts and the skip reads 100 / 17; a
skipped token costs no grouped row. None where the program has no such
gauge."""


def read(context):
    from deeplearning4j_tpu import observability as obs

    family = obs.metrics.get_family("dl4j_moe_skip_share")
    values = [c.get() for c in family.children()] if family else []
    return 100.0 * sum(values) / len(values) if values else None
