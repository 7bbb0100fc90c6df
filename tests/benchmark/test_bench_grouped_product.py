"""`grouped_product_time_share.fit`'s reader: the share of busy time in the
experts' grouped products, whichever of XLA's `ragged-dot` calls and the
registry's `grouped_matmul` kernels a program runs (PERF.md PR 35). The
reader is in the tree and `BENCHMARK.json` does not list it yet: PERF.md
section 7 says which pins a `benchmark` PR has to move with the entry."""

import types

import pytest

from benchmark.harness import cells

MS = 1e6
# 100 ms busy: three grouped products (10 + 15 + 5), an attention kernel, a
# fusion whose name only holds the words, the head
OTHERS = [("banded_attention_fwd.7 [tpu_custom_call]", 30 * MS, 20 * MS),
          ("fusion.ragged-dot-like", 50 * MS, 10 * MS),
          ("fusion.6", 60 * MS, 40 * MS)]
NAMES = {
    "xla": ["ragged-dot-none.3 [tpu_custom_call]",
            "ragged-dot-none.4 [tpu_custom_call]",
            "ragged-dot-general-none.9 [tpu_custom_call]"],
    "kernel": ["grouped_matmul_rows_table.3 [tpu_custom_call]",
               "grouped_matmul_rows_table_t.1 [tpu_custom_call]",
               "grouped_matmul_contracted.2 [tpu_custom_call]"],
    "mixed": ["ragged-dot-none.3 [tpu_custom_call]",
              "grouped_matmul_rows_table_t.1 [tpu_custom_call]",
              "grouped_matmul_contracted [tpu_custom_call]"],
}


def _context(events, busy_s=0.1):
    reduced = {"busy_s": busy_s, "window_s": 0.125,
               "events": {0: events}} if events is not None else None
    return {"tracer": types.SimpleNamespace(reduced=lambda chips: reduced),
            "executables": [], "cell": types.SimpleNamespace(chips=1)}


def _read():
    return cells.load_module("layer_metrics",
                             "grouped_product_time_share.fit").read


@pytest.mark.parametrize("program", list(NAMES))
def test_it_reads_the_same_work_under_either_name(program):
    a, b, c = NAMES[program]
    events = [(a, 0, 10 * MS), (b, 10 * MS, 15 * MS), (c, 25 * MS, 5 * MS)]
    assert _read()(_context(events + OTHERS)) == pytest.approx(30.0)


@pytest.mark.parametrize("case,events,busy_s", [
    ("no_trace", None, 0.1), ("nothing_busy", OTHERS, 0.0),
    ("no_grouped_product", OTHERS, 0.1)])
def test_it_reads_nothing_where_there_is_nothing_to_read(case, events, busy_s):
    assert _read()(_context(events, busy_s)) is None


def test_two_chips_average_their_seconds():
    a, b, _ = NAMES["kernel"]
    context = _context([(a, 0, 10 * MS)])
    context["tracer"].reduced(1)["events"][1] = [(b, 0, 30 * MS)]
    assert _read()(context) == pytest.approx(20.0)
