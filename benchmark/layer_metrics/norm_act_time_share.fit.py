"""kernels: share of the device's busy time in the Pallas `norm_act`
kernels (operations named after the kernel's `name=`: `norm_act_batchnorm`,
`norm_act_layernorm`, under `jvp(...)` in a train step), in percent. None
where no operation is so named (a program whose kernels have no names)."""

PATTERN = r"norm_act"


def read(context):
    from benchmark.harness import trace_reduce

    return trace_reduce.time_share_percent(context, PATTERN) or None
