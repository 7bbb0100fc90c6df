"""kernels: share of the device's busy time in the language model's head
and its cross-entropy (scope `lm.head`, forward and backward), in percent.
Large in a cell whose depth is cut: the head is whole, the layers are not."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "lm.head")
