"""kernels: the share of a step's (token, expert) pairs routed to the
experts this chip holds, over all dropless MoE layers, in percent, at the
last step the score was read (the mean of `dl4j_moe_pairs_held_share` over
the layers: each routes the same number of pairs). An even router reads
held / all experts (12.5 for 16 of 128); the held experts' work grows with
it. None where the program has no such gauge."""


def read(context):
    from deeplearning4j_tpu import observability as obs

    family = obs.metrics.get_family("dl4j_moe_pairs_held_share")
    values = [c.get() for c in family.children()] if family else []
    return 100.0 * sum(values) / len(values) if values else None
