"""scheduler: share of the window's wall time the decode thread spent in
prefill dispatches (`dl4j_serving_dispatch_seconds_total{phase="prefill"}`,
host wall time of prefill + install), in percent."""


def read(context):
    return 100.0 * context["delta"]["prefill_s"] / context["window_s"]
