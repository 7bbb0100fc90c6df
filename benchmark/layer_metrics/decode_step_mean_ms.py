"""model step: mean host wall time of one decode round over the window
(`dl4j_serving_decode_step_seconds`: dispatch, the wait for the device and
the copy of the `[slots, V]` distributions to the host), in ms."""


def read(context):
    total, count = context["delta"]["decode_steps"]
    return 1e3 * total / count if count else None
