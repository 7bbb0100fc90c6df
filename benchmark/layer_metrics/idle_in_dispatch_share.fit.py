"""engine: share of the traced span in which the device is idle while the
enqueuing thread is inside `<e>.iteration` (its `<e>.enqueue` included),
percent: the device waiting for the engine's dispatch."""


def read(context):
    from benchmark.harness import host_spans

    return host_spans.idle_share_percent(context,
                                         r"^(graph|mln)\.iteration$")
