"""From a `jax.profiler` trace to what the HOST was doing while the device
was idle.

The program writes its spans into the profiler's own trace
(`observability/tracing.py`: every live span is also a `TraceAnnotation`),
so they sit on `/host:CPU`, one line per thread, on the clock of the
device's `XLA Ops`. This module reads them per THREAD (two threads are both
called `python3`, and `trace_reduce.load` merges lines of one name), takes
each span's self time (its duration less what its children cover), and puts
every instant in which no device operation runs down to the innermost span
open at that instant on the enqueuing thread: the thread that records the
`*.enqueue` spans, since only what that thread does can hold the device up.
An instant with no span open there is `unnamed`: what the tracing cannot
explain (the benchmark's own sync, a thread that is not instrumented).

Like `trace_reduce.reduce_device`, everything but `load_threads` takes plain
tuples, so the CPU tests feed it hand-built traces.

  python3 benchmark/harness/host_spans.py <trace dir>

prints per thread and span name: count, total, self and attributed idle
seconds.
"""

from __future__ import annotations

import re

HOST_PLANE = "/host:CPU"
UNNAMED = "unnamed"
# A program span is named `<layer>.<what>` in lower case (`graph.enqueue`,
# `serving.decode_round`); the runtime's own host events are not
# (`PjitFunction(step_fn)`, `np.asarray(jax.Array)`).
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
ENQUEUE = re.compile(r"\.enqueue$")


def load_threads(path: str) -> dict:
    """`{thread: [(span name, start_ns, duration_ns)]}` of the host
    plane's lines that hold program spans; a thread is its line's name and
    its place among the plane's lines, so namesakes stay apart."""
    from jax.profiler import ProfileData

    threads = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            spans = [(e.name, float(e.start_ns), float(e.duration_ns))
                     for e in line.events if SPAN_NAME.match(e.name)]
            if spans:
                threads[f"{line.name}#{i}"] = spans
    return threads


def nest(spans):
    """One thread's spans -> `[(name, start, end, parent index)]` sorted by
    start, each under the innermost span that encloses it."""
    out, stack = [], []
    for start, neg_dur, name in sorted((s[1], -s[2], s[0]) for s in spans):
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        out.append((name, start, start - neg_dur,
                    stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def self_times(spans):
    """One thread's spans -> `[(name, start_ns, duration_ns, self_ns)]`:
    a span's self time is its duration less the part its direct children
    cover (children of one parent on one thread do not overlap)."""
    nested = nest(spans)
    covered = [0.0] * len(nested)
    for _, start, end, parent in nested:
        if parent is not None:
            covered[parent] += min(end, nested[parent][2]) - start
    return [(name, start, end - start, end - start - covered[i])
            for i, (name, start, end, _) in enumerate(nested)]


def timeline(spans):
    """One thread's spans -> disjoint `[(start, end, path)]` in time order,
    `path` the names open over that stretch from the outermost in; the
    stretches with no span open are left out."""
    nested = nest(spans)
    edges = sorted({t for _, start, end, _ in nested for t in (start, end)})
    out, stack, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while stack and nested[stack[-1]][2] <= t0:
            stack.pop()
        while k < len(nested) and nested[k][1] <= t0:
            if nested[k][2] > t0:  # not a span of no length
                stack.append(k)
            k += 1
        if stack:
            out.append((t0, t1, tuple(nested[i][0] for i in stack)))
    return out


def idle_gaps(device_events):
    """`[(start, end)]` within the traced span (first operation's start to
    the last one's end) in which no device operation runs."""
    from benchmark.harness.trace_reduce import merge

    busy = merge((s, s + d) for _, s, d in device_events if d > 0)
    return [(end, start) for (_, end), (start, _) in zip(busy, busy[1:])]


def idle_by_path(device_events, spans) -> dict:
    """`{path: idle ns}`: each idle instant of the device under the spans
    open then on the thread `spans` came from (`()` where none is)."""
    out = {}
    stretches = timeline(spans)
    k = 0
    for g0, g1 in idle_gaps(device_events):
        left = g1 - g0
        while k < len(stretches) and stretches[k][1] <= g0:
            k += 1
        j = k
        while j < len(stretches) and stretches[j][0] < g1:
            t0, t1, path = stretches[j]
            part = min(t1, g1) - max(t0, g0)
            out[path] = out.get(path, 0.0) + part
            left -= part
            j += 1
        if left > 0:
            out[()] = out.get((), 0.0) + left
    return out


def idle_by_span(device_events, spans) -> dict:
    """`{span name: idle ns}`: each idle instant put down to the innermost
    span open then, else to `unnamed`."""
    out = {}
    for path, ns in idle_by_path(device_events, spans).items():
        name = path[-1] if path else UNNAMED
        out[name] = out.get(name, 0.0) + ns
    return out


def idle_inside(device_events, spans, pattern: str) -> float:
    """Idle ns while a span whose name matches `pattern` (a regex,
    searched) is open at any depth."""
    rx = re.compile(pattern)
    return sum(ns for path, ns in idle_by_path(device_events, spans).items()
               if any(rx.search(name) for name in path))


def enqueuing_thread(threads: dict):
    """The thread with the most `*.enqueue` spans, or None."""
    counts = {t: sum(1 for s in spans if ENQUEUE.search(s[0]))
              for t, spans in threads.items()}
    best = max(counts, key=counts.get, default=None)
    return best if best is not None and counts[best] else None


def summarize(threads: dict, device_events) -> list:
    """Rows `[thread, span name, count, total_s, self_s, idle_s]`, idle
    attributed on the enqueuing thread only (None elsewhere), with one
    `unnamed` row for what no span covers."""
    enq = enqueuing_thread(threads)
    rows = []
    for thread, spans in sorted(threads.items()):
        per = {}
        for name, _, dur, self_ns in self_times(spans):
            c = per.setdefault(name, [0, 0.0, 0.0])
            c[0] += 1
            c[1] += dur
            c[2] += self_ns
        idle = None
        if thread == enq:
            idle = idle_by_span(device_events, spans)
            per.setdefault(UNNAMED, [0, 0.0, 0.0])
        rows += [[thread, name, count, total / 1e9, self_ns / 1e9,
                  None if idle is None else idle.get(name, 0.0) / 1e9]
                 for name, (count, total, self_ns) in sorted(per.items())]
    return rows


# ------------------------------------------------- for the metric readers


def of_run(context):
    """The traced run's host threads and device events, read once per run
    (kept in `context`): `{"threads", "enqueuing", "chips": {chip:
    events}, "window_ns": {chip: ns}}`, or None when no trace was taken or
    the program wrote no `*.enqueue` span into it."""
    if "host_spans" in context:
        return context["host_spans"]
    from benchmark.harness import trace_reduce

    found = None
    reduced = context["tracer"].reduced(context["cell"].chips)
    if reduced and reduced["events"]:
        threads = load_threads(
            trace_reduce.find_xplane(context["tracer"].directory))
        enq = enqueuing_thread(threads)
        if enq is not None:
            window = {}
            for chip, events in reduced["events"].items():
                starts = [s for _, s, d in events if d > 0]
                ends = [s + d for _, s, d in events if d > 0]
                window[chip] = max(ends) - min(starts)
            found = {"threads": threads, "enqueuing": enq,
                     "chips": reduced["events"], "window_ns": window}
    context["host_spans"] = found
    return found


def idle_share_percent(context, pattern=None):
    """Share of the traced span, in percent and averaged over the chips, in
    which the device is idle while the enqueuing thread is inside a span
    matching `pattern`; with no pattern, while it is inside none."""
    run = of_run(context)
    if run is None:
        return None
    spans = run["threads"][run["enqueuing"]]
    shares = []
    for chip, events in run["chips"].items():
        if pattern is None:
            ns = idle_by_span(events, spans).get(UNNAMED, 0.0)
        else:
            ns = idle_inside(events, spans, pattern)
        shares.append(100.0 * ns / run["window_ns"][chip])
    return sum(shares) / len(shares)


def span_mean_ms(context, pattern: str, self_time: bool = False):
    """Mean duration (or self time) in ms of the enqueuing thread's spans
    whose name matches `pattern`, over the traced span."""
    run = of_run(context)
    if run is None:
        return None
    rx = re.compile(pattern)
    values = [self_ns if self_time else dur for name, _, dur, self_ns
              in self_times(run["threads"][run["enqueuing"]])
              if rx.search(name)]
    return sum(values) / len(values) / 1e6 if values else None


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import trace_reduce

    xplane = trace_reduce.find_xplane(sys.argv[1])
    chips = trace_reduce.device_lines(trace_reduce.load(xplane))
    events = chips[min(chips)] if chips else []
    print(f"{'thread':<14}{'span':<26}{'count':>7}{'total_s':>10}"
          f"{'self_s':>10}{'idle_s':>10}")
    for thread, name, count, total, self_s, idle in summarize(
            load_threads(xplane), events):
        shown = "" if idle is None else f"{idle:10.4f}"
        print(f"{thread:<14}{name:<26}{count:>7}{total:>10.4f}"
              f"{self_s:>10.4f}{shown}")
    gaps = idle_gaps(events)
    print(f"device idle {sum(b - a for a, b in gaps) / 1e9:.4f} s in "
          f"{len(gaps)} gaps")
