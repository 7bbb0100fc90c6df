"""kernels: the largest load over the mean load among the experts a
dropless MoE layer holds, the worst layer, at the last step the score was
read (`dl4j_moe_expert_load_max_over_mean`; 1.0 is a perfectly even
router). None where the program has no such gauge."""


def read(context):
    from deeplearning4j_tpu import observability as obs

    family = obs.metrics.get_family("dl4j_moe_expert_load_max_over_mean")
    values = [c.get() for c in family.children()] if family else []
    return max(values) if values else None
