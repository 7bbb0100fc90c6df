"""kernels: share of the device's busy time in the latent attention layers'
projections (the query, the compressed key/value projection with the norm on
its latent, and its expansion: scope `mla.project`, forward and backward),
in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "mla.project")
