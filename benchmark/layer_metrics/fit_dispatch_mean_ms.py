"""engine: mean host time of one `_fit_dispatch` over the window
(`dl4j_step_dispatch_seconds`, sum/count of the window's delta)."""


def read(context):
    total, count = context["delta"]["dispatch"]
    return 1e3 * total / count if count else None
