"""`harness/scope_table.py` and its two readers, `unscoped_time_share.fit`
and `updater_time_share.fit` (PERF.md PR 36), on a hand-built trace and HLO
text: the partition of busy time by the engine's scopes with a `while` event
over its body's events, what an instruction without an `op_name` inherits,
and that a program from before the scopes reads nothing. The readers are in
the tree and `BENCHMARK.json` does not list them yet: PERF.md section 7 says
which pins a `benchmark` PR has to move with the entries."""

import json
import types

import pytest

from benchmark.harness import cells, scope_table

MS = 1e6
STEP = "jit(step_fn)/jit(main)/"
TEXT = f"""HloModule jit_step_fn, is_scheduled=true

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {{
  %param_0.1 = f32[4]{{0}} parameter(0)
  ROOT %multiply.1 = f32[4]{{0}} multiply(%param_0.1, %param_0.1), metadata={{op_name="{STEP}jvp(L.ffn0)/moe.experts/while/body/mul"}}
}}

%body.1 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {{
  %arg.1 = (s32[], f32[4]{{0}}) parameter(0)
  %get-tuple-element.1 = f32[4]{{0}} get-tuple-element(%arg.1), index=1
  %fusion.2 = f32[4]{{0}} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{STEP}jvp(L.ffn0)/moe.experts/while/body/mul" source_file="x.py"}}
  %get-tuple-element.2 = s32[] get-tuple-element(%arg.1), index=0
  ROOT %tuple.2 = (s32[], f32[4]{{0}}) tuple(%get-tuple-element.2, %fusion.2)
}}

ENTRY %main.1 (params__attn0____Wq__.1: f32[4], x.1: f32[4]) -> (f32[4], f32[4]) {{
  %params__attn0____Wq__.1 = f32[4]{{0}} parameter(0), metadata={{op_name="params[\\'attn0\\'][\\'Wq\\']"}}
  %x.1 = f32[4]{{0:T(8,128)(2,1)}} parameter(1), metadata={{op_name="x"}}
  %constant.1 = f32[4]{{0}} constant({{1, 2, 3, 4}})
  %copy.1 = f32[4]{{0:T(8,128)(2,1)}} copy(%params__attn0____Wq__.1), metadata={{op_name="params[\\'attn0\\'][\\'Wq\\']"}}
  %fusion.1 = f32[4]{{0}} fusion(%x.1, %copy.1), kind=kOutput, calls=%fc.1, metadata={{op_name="{STEP}jvp(L.attn0)/dsa.attend/dot_general"}}
  %tuple.1 = (s32[], f32[4]{{0}}) tuple(%constant.0, %fusion.1)
  %while.1 = (s32[], f32[4]{{0}}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={{op_name="{STEP}jvp(L.ffn0)/moe.experts/while"}}
  %fusion.5 = f32[4]{{0}} fusion(%while.1), kind=kLoop, calls=%fc.5, metadata={{op_name="{STEP}transpose(jvp(L.attn0))/dsa.attend/dot_general"}}
  %add_any.3 = f32[4]{{0}} add(%fusion.5, %fusion.1), metadata={{op_name="{STEP}transpose(jvp())/add_any"}}
  %fusion.9 = f32[4]{{0}} fusion(%add_any.3), kind=kLoop, calls=%fc.9, metadata={{op_name="{STEP}step.update/L.ffn0/sub"}}
  %copy.2 = f32[4]{{0}} copy(%fusion.9)
  %copy.7 = f32[4]{{0}} copy(%constant.1)
  ROOT %tuple.9 = (f32[4]{{0}}, f32[4]{{0}}) tuple(%copy.2, %copy.7)
}}
"""
# the same program as it was before the engine opened a scope
OLD_TEXT = TEXT.replace("jvp(L.attn0)", "jvp()").replace(
    "jvp(L.ffn0)", "jvp()").replace("step.update/L.ffn0/", "")


def _step(at):
    """One step of 98 ms busy in 100: a `while` of 30 ms over three runs of
    its body's one fusion, 8 ms each."""
    o = at * 100 * MS
    return [("copy.1", o, 2 * MS), ("fusion.1", o + 2 * MS, 18 * MS),
            ("while.1", o + 20 * MS, 30 * MS),
            ("fusion.2", o + 21 * MS, 8 * MS),
            ("fusion.2", o + 30 * MS, 8 * MS),
            ("fusion.2", o + 39 * MS, 8 * MS),
            ("fusion.5", o + 50 * MS, 20 * MS),
            ("add_any.3", o + 70 * MS, 4 * MS),
            ("fusion.9 [tpu_custom_call]", o + 74 * MS, 16 * MS),
            ("copy.2", o + 90 * MS, 5 * MS), ("copy.7", o + 95 * MS, 3 * MS)]


EVENTS = _step(0) + _step(1)


def _context(events=EVENTS, text=TEXT, busy_s=0.196, directory=None):
    reduced = {"busy_s": busy_s, "window_s": 0.2,
               "events": {0: events}} if events is not None else None
    exes = [] if text is None else [types.SimpleNamespace(
        as_text=lambda: text)]
    return {"tracer": types.SimpleNamespace(reduced=lambda chips: reduced,
                                            directory=directory),
            "executables": exes,
            "cell": types.SimpleNamespace(chips=1, name="a.cell")}


def _rows(table):
    return {(scope, side, inner): ms for scope, side, inner, ms
            in table["rows"]}


def test_the_rows_sum_to_the_busy_time_with_a_while_over_its_body():
    table = scope_table.table(_context())
    assert table["steps"] == 2
    assert table["busy_ms_a_step"] == pytest.approx(98.0)
    assert sum(_rows(table).values()) == pytest.approx(98.0)
    # the loop's 30 ms once: 24 in its body's fusion, 6 its own
    assert _rows(table)[("L.ffn0", "fwd", "moe.experts")] == pytest.approx(30)
    ops = {name: ms for name, ms, *_ in table["ops"]}
    assert ops["fusion.2"] == pytest.approx(24.0)
    assert ops["while.1"] == pytest.approx(6.0)


@pytest.mark.parametrize("row,ms", [
    # copy.1 has no op_name but the argument's: its consumer fusion.1 runs
    # under L.attn0
    (("L.attn0", "fwd", "dsa.attend"), 2 + 18),
    (("L.attn0", "bwd", "dsa.attend"), 20),
    # copy.2 feeds only the nameless root: its operand's producer, fusion.9
    (("step.update/L.ffn0", "fwd", ""), 16 + 5),
    # an op_name without an engine scope stays what it is
    ((scope_table.UNSCOPED, "bwd", ""), 4),
    # copy.7 of a constant: no walk finds a name
    ((scope_table.UNNAMED, "", ""), 3)])
def test_a_row_holds_what_the_rules_put_there(row, ms):
    assert _rows(scope_table.table(_context()))[row] == pytest.approx(ms)


@pytest.mark.parametrize("name,op_name", [
    ("copy.1", STEP + "jvp(L.attn0)/dsa.attend/dot_general"),
    ("copy.2", STEP + "step.update/L.ffn0/sub"),
    ("copy.7", ""), ("fusion.77", None),
    # an argument's own name (`params['attn0']['Wq']`) names no scope
    ("params__attn0____Wq__.1", STEP + "jvp(L.attn0)/dsa.attend/dot_general"),
    ("tuple.1", STEP + "jvp(L.ffn0)/moe.experts/while")])
def test_an_instruction_without_an_op_name_inherits(name, op_name):
    names = scope_table.Names(scope_table.instructions(TEXT))
    assert names.of(name) == op_name


def test_the_text_is_parsed_through_layouts_and_tuple_shapes():
    parsed = scope_table.instructions(TEXT)
    assert parsed["fusion.1"][1] == ["x.1", "copy.1"]
    assert parsed["while.1"][1] == ["tuple.1"]
    assert parsed["tuple.9"] == ("", ["copy.2", "copy.7"])
    assert parsed["multiply.1"][1] == ["param_0.1", "param_0.1"]
    # operands printed with their shapes, and without the % sign
    bare = scope_table.instructions(
        "  ROOT t.1 = (f32[2]{0}, s32[]) tuple(f32[2]{0:T(2,128)} a.1, "
        "(s32[], s32[]) b.2), metadata={op_name=\"jit(f)/L.v/tuple\"}")
    assert bare["t.1"] == ("jit(f)/L.v/tuple", ["a.1", "b.2"])


@pytest.mark.parametrize("op_name,row", [
    (STEP + "jvp(L.attn2)/attn.sliding/pallas_call",
     ("L.attn2", "fwd", "attn.sliding")),
    (STEP + "transpose(jvp(L.out))/lm.head/dot_general",
     ("L.out", "bwd", "lm.head")),
    (STEP + "transpose(jvp(L.ffn1))/moe.experts/jit(_where)/select_n",
     ("L.ffn1", "bwd", "moe.experts")),
    (STEP + "jvp(L.attn0)/mla.project/attn.rope/mul",
     ("L.attn0", "fwd", "attn.rope")),
    (STEP + "transpose(jvp(L.attn1))/mla.attend/transpose(jvp(L.attn1))/mul",
     ("L.attn1", "bwd", "mla.attend")),
    (STEP + "step.update/L.stem_conv/jit(fused_update)/pallas_call",
     ("step.update/L.stem_conv", "fwd", "")),
    (STEP + "step.grad_cast/convert_element_type",
     ("step.grad_cast", "fwd", "")),
    ("jit(step_fn)/jit(_threefry_split)/Engine._build_jit.<locals>.step_fn"
     "/while/body/closed_call/add", (scope_table.UNSCOPED, "fwd", "")),
    ("", (scope_table.UNNAMED, "", "")),
    (None, (scope_table.UNMATCHED, "", ""))])
def test_an_op_name_is_placed_by_its_pieces_alone(op_name, row):
    assert scope_table.row_of(op_name) == row


@pytest.mark.parametrize("case,kwargs", [
    ("no_trace", {"events": None}), ("nothing_busy", {"busy_s": 0.0}),
    ("no_text", {"text": None}), ("no_engine_scope", {"text": OLD_TEXT})])
def test_nothing_is_read_where_there_is_nothing_to_read(case, kwargs):
    context = _context(**kwargs)
    assert scope_table.table(context) is None
    for name in ("unscoped_time_share.fit", "updater_time_share.fit"):
        assert cells.load_module("layer_metrics", name).read(context) is None


@pytest.mark.parametrize("name,value", [
    ("unscoped_time_share.fit", 100.0 * (4 + 3) / 98),
    ("updater_time_share.fit", 100.0 * (16 + 5) / 98)])
def test_the_readers_read_the_table(name, value):
    context = _context()
    read = cells.load_module("layer_metrics", name).read
    assert read(context) == pytest.approx(value)
    assert context["scope_table"] is scope_table.table(context)  # built once


def test_an_event_the_text_does_not_hold_is_unmatched_and_outside():
    events = EVENTS + [("fusion.77", 98 * MS, 2 * MS)]
    table = scope_table.table(_context(events, busy_s=0.198))
    assert _rows(table)[(scope_table.UNMATCHED, "", "")] == pytest.approx(1.0)
    assert table["unscoped_percent"] == pytest.approx(100 * (7 + 1) / 99)
    assert [op[0] for op in table["left_over"]] == [
        "add_any.3", "copy.7", "fusion.77"]


def test_two_chips_average():
    context = _context()
    context["tracer"].reduced(1)["events"][1] = [
        ("fusion.1", 0, 40 * MS), ("fusion.1", 100 * MS, 40 * MS)]
    context["cell"].chips = 2
    table = scope_table.table(context)
    assert table["busy_ms_a_step"] == pytest.approx((98 + 40) / 2)
    assert _rows(table)[("L.attn0", "fwd", "dsa.attend")] == pytest.approx(
        (20 + 40) / 2)


@pytest.mark.parametrize("events,expected", [
    # a child that outlives its parent, and a gap
    ([("a", 0, 10), ("b", 5, 10), ("c", 20, 5)], {"a": 5, "b": 10, "c": 5}),
    # two levels of nesting and a second child
    ([("w", 0, 100), ("x", 10, 50), ("y", 20, 10), ("z", 70, 10)],
     {"w": 40, "x": 40, "y": 10, "z": 10}),
    ([("a", 0, 0)], {})])
def test_every_instant_is_counted_once(events, expected):
    assert scope_table.self_times(events) == expected


def test_the_table_is_written_beside_the_trace_and_prints(tmp_path):
    table = scope_table.table(_context(directory=str(tmp_path)))
    with open(tmp_path / scope_table.FILE) as f:
        doc = json.load(f)
    assert doc["rows"] == [list(r) for r in table["rows"]]
    assert doc["seconds_to_build"] >= 0.0
    text = scope_table.printed(doc)
    assert "a.cell: 2 steps of 98.00 ms busy" in text
    assert "step.update/L.ffn0" in text and "moe.experts" in text
