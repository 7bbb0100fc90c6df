"""kernels: share of the device's busy time in the shared expert of the expert
layers (one gated SiLU MLP over every token, in token order: scope
`moe.shared`, forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "moe.shared")
