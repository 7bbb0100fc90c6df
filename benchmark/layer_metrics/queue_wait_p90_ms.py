"""entry + admission: 90th percentile over the window's completed requests
of the time between submission to the scheduler and admission to a slot
(the request ledger's `queue_wait_s`), in ms."""


def read(context):
    from benchmark.harness import stats

    waits = [float(r["queue_wait_s"]) for r in context["completed"]]
    return 1e3 * stats.percentile(waits, 90) if waits else None
