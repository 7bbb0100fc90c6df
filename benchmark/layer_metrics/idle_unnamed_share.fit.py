"""device: share of the traced span in which the device is idle and the
enqueuing thread has no program span open, percent: what the tracing cannot
explain (the benchmark's own sync between groups of epochs included)."""


def read(context):
    from benchmark.harness import host_spans

    return host_spans.idle_share_percent(context)
