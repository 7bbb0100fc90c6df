"""kernels: share of the device's busy time in the sparse attention
(operations traced under the scopes `dsa.indexer`, `dsa.select`,
`dsa.attend`, forward and backward), in percent."""


def read(context):
    from benchmark.harness import scope_time

    return scope_time.scope_share_percent(context, "dsa.")
