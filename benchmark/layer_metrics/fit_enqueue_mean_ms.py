"""engine: mean host time of the compiled train step's call alone (the
program's `<e>.enqueue` spans on the enqueuing thread, over the traced
span), in ms."""


def read(context):
    from benchmark.harness import host_spans

    return host_spans.span_mean_ms(context, r"^(graph|mln)\.enqueue$")
