"""kernels: share of the device's busy time in the elementwise and
per-head work of the compressed convolutional attention layers between
their projections and their core (both causal convolutions, the q-k mean,
the L2 norms with the temperature, the rotary step: scope `cca.conv`,
forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "cca.conv")
