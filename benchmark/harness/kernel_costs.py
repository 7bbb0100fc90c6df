"""Operations a kernel's layer needs, computed from the configuration's
sizes alone: the numerator of a `<kernel>_roofline` share. Nothing here
looks at a kernel's tiles, blocks or name, so the count is the same
whatever implements the layer, and a tile that holds pairs the band masks
is time without operations.

Attention over a causal band, forward and backward: 9 product passes over
the (query, key) pairs inside the band (2 forward: q k^T and p v; 3 in dq:
q k^T, do v^T, ds k; 4 in dk/dv: k q^T, p^T do, v do^T, ds^T q: the flash
formulation's recomputation is work the method needs), each 2 * Dh FLOP a
pair and a head. The same 9 passes are `masked_attention`'s count in
PERF.md section 7 (78% by hand at PR 27), so the two stay comparable.
"""

from __future__ import annotations

ATTENTION_TRAIN_PASSES = 9


def band_pairs(S: int, window=None) -> int:
    """(query, key) pairs with key <= query, and query - window < key under
    a window: row t has min(t + 1, window) of them."""
    w = S if window is None else min(int(window), S)
    return w * (w + 1) // 2 + (S - w) * w


def attention_train_flops(S: int, n_heads: int, head_dim: int,
                          window=None) -> int:
    """FLOP of one attention layer's forward and backward pass over one
    sequence of S positions."""
    return (ATTENTION_TRAIN_PASSES * 2 * head_dim * n_heads
            * band_pairs(S, window))


def attention_step_flops(sizes: dict) -> int:
    """FLOP of all attention layers of one train step of a configuration
    whose file gives `layer_types` (its first `num_hidden_layers` entries
    are the layers held), `sliding_window`, `seq_len`, `batch_per_chip`,
    `num_attention_heads` and `head_dim`."""
    S = int(sizes["seq_len"])
    kinds = sizes["layer_types"][:int(sizes["num_hidden_layers"])]
    per_sequence = sum(
        attention_train_flops(
            S, int(sizes["num_attention_heads"]), int(sizes["head_dim"]),
            int(sizes["sliding_window"]) if kind == "sliding_attention"
            else None)
        for kind in kinds)
    return int(sizes["batch_per_chip"]) * per_sequence


def scoped_seconds_and_steps(context, needles):
    """(seconds of device time under the scopes `needles`, averaged over the
    chips used; train steps in the trace), or None where there is no trace,
    no program text or no operation under them. The steps are counted from
    the trace itself: the scoped instruction that takes most time runs once
    a step (a kernel, or the `while` around a body of row blocks), and the
    trace starts and stops where the driver has synced, so it holds whole
    steps."""
    from benchmark.harness import scope_time

    reduced = context["tracer"].reduced(context["cell"].chips)
    if not reduced or not reduced["busy_s"]:
        return None
    names = scope_time.op_names(context.get("executables") or [])
    scoped = {name for name, op in names.items()
              if any(needle in op for needle in needles)}
    if not scoped:
        return None
    seconds, steps = [], []
    for events in reduced["events"].values():
        total, count = {}, {}
        for name, _, duration in events:
            key = name.split(" ", 1)[0]
            if key in scoped and duration > 0:
                total[key] = total.get(key, 0.0) + duration
                count[key] = count.get(key, 0) + 1
        if not total:
            return None
        steps.append(count[max(total, key=total.get)])
        seconds.append(sum(scope_time.seconds_in_scope(events, names, needle)
                           for needle in needles))
    return sum(seconds) / len(seconds), min(steps)
