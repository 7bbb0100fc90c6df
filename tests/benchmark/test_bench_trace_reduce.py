"""The reduction from a profiler trace to busy time, gaps and operation
times, on a small hand-built trace. No topology and no TPU call anywhere in
this file: the trace is written as a text proto and read back through the
same `jax.profiler.ProfileData` path a chip run uses."""

import os

import pytest

from benchmark.harness import trace_reduce

MS = 1_000_000  # ns

# name, start ms, duration ms on chip 0's `XLA Ops` line
OPS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop", 0, 2),
    ("%custom-call.3 = bf16[8,128]{1,0} custom-call(bf16[8,128]{1,0} %x), "
     "custom_call_target=\"tpu_custom_call\"", 2, 3),
    # overlaps the custom call's tail: the union counts it once
    ("%copy.2 = f32[8]{0} copy(f32[8]{0} %y)", 4, 2),
    # 4 ms of nothing, then
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop", 10, 2),
    # 1 ms of nothing, then
    ("%convolution.9 = bf16[8,8]{1,0} convolution(...)", 13, 7),
]


def xspace_text():
    meta, events = [], []
    names = sorted({n for n, _, _ in OPS})
    ids = {n: i + 1 for i, n in enumerate(names)}
    for n, i in ids.items():
        escaped = n.replace('"', '\\"')
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{escaped}" }} }}')
    for n, start, dur in OPS:
        events.append(f"events {{ metadata_id: {ids[n]} "
                      f"offset_ps: {start * MS * 1000} "
                      f"duration_ps: {dur * MS * 1000} }}")
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 5000 {' '.join(events)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 5000
           events {{ metadata_id: 1 offset_ps: 0 duration_ps: {20 * MS * 1000} }} }}
  {' '.join(meta)}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 5000
           events {{ metadata_id: 1 offset_ps: 0 duration_ps: {50 * MS * 1000} }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "PjitFunction(step)" }} }}
}}
"""


@pytest.fixture(scope="module")
def planes(tmp_path_factory):
    from jax.profiler import ProfileData

    run = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    path = run / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        xspace_text()))
    trace_dir = str(run.parent.parent.parent)
    assert trace_reduce.find_xplane(trace_dir) == str(path)
    return trace_reduce.load(str(path))


def test_load_finds_the_device_plane_and_shortens_names(planes):
    assert set(planes) == {"/device:TPU:0", "/host:CPU"}
    per_chip = trace_reduce.device_lines(planes)
    assert list(per_chip) == [0]
    names = [n for n, _, _ in per_chip[0]]
    assert names == ["fusion.1", "custom-call.3 [tpu_custom_call]", "copy.2",
                     "fusion.1", "convolution.9"]


def test_busy_is_the_union_and_the_window_is_the_span(planes):
    r = trace_reduce.reduce_trace(planes, chips=1)
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.015)  # 6 + 2 + 7 ms
    assert r["device_ops"][0] == ["convolution.9", pytest.approx(0.007)]
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == \
        pytest.approx(0.004)


def test_idle_gaps_longest_first_named_by_what_ran_before(planes):
    gaps = trace_reduce.reduce_trace(planes, chips=1)["idle_gaps"]
    assert [g[1] for g in gaps] == [pytest.approx(0.004),
                                    pytest.approx(0.001)]
    assert gaps[0][0] == "+0.006s after copy.2"
    assert gaps[1][0] == "+0.012s after fusion.1"


def test_time_in_pallas_kernels(planes):
    events = trace_reduce.device_lines(planes)[0]
    assert trace_reduce.time_matching(events, r"\[tpu_custom_call\]") == \
        pytest.approx(0.003)


def test_no_device_plane_reduces_to_nothing():
    r = trace_reduce.reduce_trace({"/host:CPU": {"python3": [("x", 0, 5)]}},
                                  chips=1)
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


@pytest.mark.parametrize("intervals,want", [
    ([(0, 1), (1, 2)], [[0, 2]]),
    ([(5, 6), (0, 1)], [[0, 1], [5, 6]]),
    ([(0, 10), (2, 3), (9, 12)], [[0, 12]]),
    ([], []),
])
def test_merge(intervals, want):
    assert trace_reduce.merge(intervals) == want


def test_a_plane_without_an_ops_line_uses_all_its_lines():
    planes = {"/device:TPU:1": {"Steps": [("1", 0.0, 5.0)],
                                "Other": [("x", 10.0, 5.0)]}}
    events = trace_reduce.device_lines(planes)[1]
    assert len(events) == 2
    r = trace_reduce.reduce_device(events)
    assert r["busy_s"] == pytest.approx(10e-9)


def test_describe_lists_lines_and_heaviest_names(planes):
    d = trace_reduce.describe(planes)
    assert d["/device:TPU:0"]["XLA Ops"]["events"] == 5
    assert d["/device:TPU:0"]["XLA Ops"]["top"][0][0] == "convolution.9"
    assert os.path.basename(trace_reduce.__file__) == "trace_reduce.py"
