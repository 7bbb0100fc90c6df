"""kernels: share of the device's busy time in the dense gated SiLU MLP of the
leading layers (scope `ffn.dense`, forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "ffn.dense")
