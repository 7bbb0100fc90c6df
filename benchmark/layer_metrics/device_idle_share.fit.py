"""device: share of the traced window in which no operation ran, percent."""


def read(context):
    from benchmark.harness import trace_reduce

    return trace_reduce.idle_share_percent(context)
