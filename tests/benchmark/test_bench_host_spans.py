"""The program's spans on the device's clock: self times, and each idle
instant of the device put down to what the enqueuing thread was doing. On
hand-built tuples, and once through a text-proto trace and the six readers
that `BENCHMARK.json` lists. No topology and no TPU call in this file."""

import types

import pytest

from benchmark.harness import cells, host_spans, trace_reduce

MS = 1_000_000  # ns

READERS = ["fit_enqueue_mean_ms", "fit_dispatch_self_mean_ms",
           "idle_in_dispatch_share.fit", "idle_in_input_wait_share.fit",
           "idle_unnamed_share.fit", "pallas_time_share.fit"]


def ms(spans):
    return [(name, start * MS, dur * MS) for name, start, dur in spans]


# One epoch of two steps on the caller's thread, in ms. The stager's thread
# has the same line name and a span that overlaps the caller's.
CALLER = ms([
    ("graph.fit", 10, 80),
    ("graph.input_wait", 12, 6),
    ("graph.iteration", 20, 20),
    ("graph.enqueue", 30, 8),
    ("graph.input_wait", 41, 1),
    ("graph.iteration", 44, 30),
    ("graph.enqueue", 50, 20),
])
STAGER = ms([("staging.put", 11, 30), ("staging.put", 45, 10)])
# The device: busy 0-5, 38-60, 72-100; idle 5-38 (33 ms) and 60-72 (12 ms).
# (`norm_act_time_share.fit` read the second event until PR 34 took the metric
# out; it is a Pallas call now, for the reader that stayed.)
PALLAS = "masked_attention_fwd.3 = custom-call(x), target=tpu_custom_call"
DEVICE = ms([("fusion.1", 0, 5), (PALLAS, 38, 12),
             ("fusion.2", 50, 10), ("fusion.1", 72, 28)])


def test_self_time_is_duration_less_the_children():
    by_name = {}
    for name, _, dur, self_ns in host_spans.self_times(CALLER):
        got = by_name.setdefault(name, [0.0, 0.0])
        got[0] += dur / MS
        got[1] += self_ns / MS
    assert by_name["graph.enqueue"] == [28.0, 28.0]
    assert by_name["graph.iteration"] == [50.0, 22.0]
    assert by_name["graph.input_wait"] == [7.0, 7.0]
    assert by_name["graph.fit"] == [80.0, 23.0]  # 80 - 50 - 7


def test_a_gap_is_split_at_span_boundaries():
    idle = {k: v / MS for k, v in
            host_spans.idle_by_span(DEVICE, CALLER).items()}
    # 5-38: nothing open until 10, fit's own 10-12 and 18-20, input_wait
    # 12-18, iteration's own 20-30, enqueue 30-38. 60-72: enqueue until
    # 70, iteration's own after.
    assert idle == {"unnamed": 5.0, "graph.fit": 4.0,
                    "graph.input_wait": 6.0, "graph.iteration": 12.0,
                    "graph.enqueue": 18.0}
    assert sum(idle.values()) == 45.0
    assert host_spans.idle_inside(DEVICE, CALLER, r"\.iteration$") == 30 * MS
    assert host_spans.idle_inside(DEVICE, CALLER, r"\.input_wait$") == 6 * MS
    assert host_spans.idle_gaps(DEVICE) == [(5 * MS, 38 * MS),
                                            (60 * MS, 72 * MS)]


def test_a_gap_with_no_span_is_unnamed():
    spans = ms([("graph.fit", 200, 10), ("graph.enqueue", 201, 1)])
    assert host_spans.idle_by_span(DEVICE, spans) == {"unnamed": 45 * MS}
    assert host_spans.idle_by_span(DEVICE, []) == {"unnamed": 45 * MS}
    assert host_spans.idle_by_span([], CALLER) == {}


def test_threads_of_one_name_stay_apart_and_the_enqueuer_is_found():
    threads = {"python3#0": STAGER, "python3#1": CALLER}
    assert host_spans.enqueuing_thread(threads) == "python3#1"
    assert host_spans.enqueuing_thread({"python3#0": STAGER}) is None
    rows = host_spans.summarize(threads, DEVICE)
    stager = [r for r in rows if r[0] == "python3#0"]
    assert stager == [["python3#0", "staging.put", 2, 0.040, 0.040, None]]
    caller = {r[1]: r for r in rows if r[0] == "python3#1"}
    assert caller["graph.enqueue"][2:] == [2, 0.028, 0.028, 0.018]
    assert caller["unnamed"][5] == 0.005
    assert sum(r[5] for r in caller.values()) == pytest.approx(0.045)


def xspace_text():
    """CALLER, STAGER and DEVICE as a trace: two host lines called
    `python3`, runtime events among the spans, one chip."""
    def line(lid, name, spans, ids):
        events = " ".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s) * 1000} "
            f"duration_ps: {int(d) * 1000} }}" for n, s, d in spans)
        return f'lines {{ id: {lid} name: "{name}" {events} }}'

    def meta(ids):
        return " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())

    runtime = [("PjitFunction(step_fn)", 30 * MS, 8 * MS),
               ("np.asarray(jax.Array)", 90 * MS, 1 * MS)]
    host = {n: i + 1 for i, n in enumerate(sorted(
        {s[0] for s in CALLER + STAGER + runtime}))}
    dev = {n: i + 1 for i, n in enumerate(sorted({s[0] for s in DEVICE}))}
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  {line(1, "XLA Ops", DEVICE, dev)} {meta(dev)} }}
planes {{ id: 2 name: "/host:CPU"
  {line(1, "python3", STAGER, host)}
  {line(2, "python3", CALLER + runtime, host)} {meta(host)} }}
"""


@pytest.fixture(scope="module")
def context(tmp_path_factory):
    from jax.profiler import ProfileData

    trace_dir = tmp_path_factory.mktemp("trace")
    run = trace_dir / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(xspace_text()))
    tracer = trace_reduce.WindowTracer(str(trace_dir))
    tracer.state = "done"  # as after `finish()` of a traced window
    return {"tracer": tracer, "cell": types.SimpleNamespace(chips=1)}


def test_load_keeps_namesake_threads_apart_and_only_program_spans(context):
    threads = host_spans.load_threads(
        trace_reduce.find_xplane(context["tracer"].directory))
    assert sorted(threads) == ["python3#0", "python3#1"]
    assert sorted(threads["python3#0"]) == sorted(STAGER)
    assert sorted(threads["python3#1"]) == sorted(CALLER)


def test_readers_on_a_trace_and_shares_sum_to_the_idle_share(context):
    read = {name: cells.load_module("layer_metrics", name).read(context)
            for name in READERS + ["device_idle_share.fit"]}
    assert read["fit_enqueue_mean_ms"] == pytest.approx(14.0)
    assert read["fit_dispatch_self_mean_ms"] == pytest.approx(11.0)
    assert read["idle_in_dispatch_share.fit"] == pytest.approx(30.0)
    assert read["idle_in_input_wait_share.fit"] == pytest.approx(6.0)
    assert read["idle_unnamed_share.fit"] == pytest.approx(5.0)
    assert read["pallas_time_share.fit"] == pytest.approx(100 * 12 / 55)
    run = host_spans.of_run(context)
    in_fit_itself = host_spans.idle_by_span(
        run["chips"][0], run["threads"][run["enqueuing"]])["graph.fit"]
    assert (read["idle_in_dispatch_share.fit"]
            + read["idle_in_input_wait_share.fit"]
            + read["idle_unnamed_share.fit"]
            + 100.0 * in_fit_itself / run["window_ns"][0]
            ) == pytest.approx(read["device_idle_share.fit"])


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_without_a_trace(name):
    context = {"tracer": trace_reduce.WindowTracer(None),
               "cell": types.SimpleNamespace(chips=1)}
    assert cells.load_module("layer_metrics", name).read(context) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_where_the_program_wrote_no_spans(
        name, tmp_path):
    """What the parent commit's trace looks like: device operations, none
    of them named after a kernel, and no program span on the host."""
    from jax.profiler import ProfileData

    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace("""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "jvp__.3" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "PjitFunction(step_fn)" } } }
"""))
    tracer = trace_reduce.WindowTracer(str(tmp_path))
    tracer.state = "done"
    context = {"tracer": tracer, "cell": types.SimpleNamespace(chips=1)}
    got = cells.load_module("layer_metrics", name).read(context)
    # the span readers find nothing to read; a share of the busy time in
    # Pallas calls is read, and is nought
    assert got == (0.0 if name == "pallas_time_share.fit" else None)
