"""kernels: share of the device's busy time in the experts' grouped
products, in percent: the operations the TPU compiler names `ragged-dot...`
(its lowering of `jax.lax.ragged_dot`) or `grouped_matmul...` (the
registry's kernel of that name, `deeplearning4j_tpu/kernels/
grouped_matmul.py`), so it reads the same work whichever of the two a
program runs. None of them holds another, so their times add. Where the
trace holds neither, nothing is read."""

PATTERN = r"^(ragged-dot|grouped_matmul)"


def read(context):
    from benchmark.harness import trace_reduce

    return trace_reduce.time_share_percent(context, PATTERN) or None
