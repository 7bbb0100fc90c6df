"""updater seam + kernels: what the COMPILED train step moves, as XLA's cost
analysis counts it ("bytes accessed", GB). A count of what this program
does, not of what the mathematics needs: never the numerator of a roofline
share (it is blind inside a `tpu_custom_call`)."""


def read(context):
    for exe in context["executables"]:
        try:
            cost = exe.cost_analysis()
        except Exception:  # a plain jit callable, or a loaded executable
            continue
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        nbytes = float((cost or {}).get("bytes accessed", 0.0))
        if nbytes > 0:
            return nbytes / 1e9
    return None
