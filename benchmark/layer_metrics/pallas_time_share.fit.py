"""kernels: share of the device's busy time spent in Pallas kernels
(operations whose HLO names `tpu_custom_call`), in percent."""

PATTERN = r"\[tpu_custom_call\]"  # the tag `trace_reduce.short_name` sets


def read(context):
    from benchmark.harness import trace_reduce

    return trace_reduce.time_share_percent(context, PATTERN)
