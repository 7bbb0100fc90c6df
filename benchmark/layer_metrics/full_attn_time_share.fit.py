"""kernels: share of the device's busy time in the attention of the full
(causal, no window) layers (operations traced under the scope `attn.full`,
forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "attn.full")
