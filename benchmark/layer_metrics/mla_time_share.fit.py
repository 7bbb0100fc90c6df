"""kernels: share of the device's busy time in the core of the latent attention
layers (the score as its two products, the softmax, the values: operations
traced under the scope `mla.attend`, forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "mla.attend")
