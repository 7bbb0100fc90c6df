"""scheduler: share of the window's slot-seconds held by the requests that
completed in it (ledger: sum of duration - queue wait, over window x
slots), in percent. A request that began before the window brings its
whole time in, so a steady closed loop reads a little over its true
occupancy."""


def read(context):
    from benchmark.harness import stats

    if not context["completed"]:
        return None
    return 100.0 * stats.slot_occupancy(
        context["completed"], context["window_s"], context["slots"])
