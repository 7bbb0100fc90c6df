"""input pipeline: share of the traced span in which the device is idle
while the enqueuing thread is inside `<e>.input_wait` (`next()` of the
batch source), percent: the device waiting for input."""


def read(context):
    from benchmark.harness import host_spans

    return host_spans.idle_share_percent(context,
                                         r"^(graph|mln)\.input_wait$")
