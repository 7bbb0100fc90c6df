"""kernels: share of the device's busy time in the compressed convolutional
attention layers (the projections, the value shift, both convolutions, the
q-k mean, the norms, the rotary step and the core: operations traced under
the scopes `cca.project`, `cca.conv` and `cca.attend`, forward and
backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "cca.")
