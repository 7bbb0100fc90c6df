"""engine: mean self time of one `_fit_dispatch` (the program's
`<e>.iteration` spans less what their children cover: `<e>.enqueue`), over
the traced span, in ms. The engine's own host work per step: argument
handling, listeners, the flight record."""


def read(context):
    from benchmark.harness import host_spans

    return host_spans.span_mean_ms(context, r"^(graph|mln)\.iteration$",
                                   self_time=True)
