"""From a `jax.profiler` trace (xplane) to device busy time, idle gaps and
per-operation time.

What a v5e trace looks like (looked at by hand, PERF.md section 3 "Device"):
one plane per chip named `/device:TPU:<n>`; its line `XLA Ops` holds one
event per executed HLO operation (a Pallas kernel is an operation like any
other, under the name of its custom call), `XLA Modules` one event per
executed program, `Steps` one per program run. Host threads are lines of
`/host:CPU`. Busy time is the union of the `XLA Ops` intervals of a chip;
where a device plane has no such line, the union of all of its lines.

The reduction takes plain tuples, so that the CPU tests can feed it a
hand-built trace; `load` is the only function that touches the profiler.
"""

from __future__ import annotations

import glob
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """`{plane name: {line name: [(event name, start_ns, duration_ns)]}}`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return planes


def short_name(text: str) -> str:
    """An operation's event carries its whole HLO line; its name is what
    stands before " = ". A Pallas kernel is a custom call whose target is
    `tpu_custom_call`: that is kept as a tag, since the HLO name alone
    (`%custom-call.7`, or whatever the compiler chose) does not say it."""
    name = text.split(" = ", 1)[0].lstrip("%")[:120]
    if "tpu_custom_call" in text:
        name += " [tpu_custom_call]"
    return name


def device_lines(planes: dict, line: str = OPS_LINE) -> dict:
    """`{chip index: events}` of the device planes: the events of `line`,
    or of every line of a plane that has no such line."""
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        events = lines.get(line)
        if events is None:
            events = [e for evs in lines.values() for e in evs]
        out[int(m.group(1))] = events
    return out


def merge(intervals):
    """Sorted disjoint `[start, end]` intervals covering `intervals`."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def reduce_device(events, top: int = 10, gaps: int = 10) -> dict:
    """One chip's events -> busy seconds, the traced span, time per
    operation name, and the longest gaps in which nothing ran."""
    spans = [(s, s + d) for _, s, d in events if d > 0]
    if not spans:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": [], "gaps": []}
    merged = merge(spans)
    t0, t1 = merged[0][0], merged[-1][1]
    busy = sum(e - s for s, e in merged)
    per_op = {}
    for name, _, d in events:
        per_op[name] = per_op.get(name, 0.0) + d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    # What ran last before each gap: the gap is named by it and by its
    # start offset. Naming what the HOST did in a gap needs annotations
    # inside the program (PERF.md, for the tracing issue).
    ends = sorted((s + d, name) for name, s, d in events if d > 0)
    idle = []
    k = 0
    for (_, end), (start, _) in zip(merged, merged[1:]):
        while k + 1 < len(ends) and ends[k + 1][0] <= end:
            k += 1
        idle.append((start - end, end, ends[k][1]))
    idle.sort(reverse=True)
    return {
        "busy_s": busy / 1e9, "window_s": (t1 - t0) / 1e9,
        "ops": [[n, d / 1e9] for n, d in ops],
        "gaps": [[f"+{(end - t0) / 1e9:.3f}s after {name}", gap / 1e9]
                 for gap, end, name in idle[:gaps]],
    }


def time_matching(events, pattern: str) -> float:
    """Seconds in events whose name matches `pattern` (a regex, searched)."""
    rx = re.compile(pattern)
    return sum(d for name, _, d in events if rx.search(name)) / 1e9


def reduce_trace(planes: dict, chips: int) -> dict:
    """All chips used -> what the last line's `device` and `breakdown`
    carry: busy seconds and window averaged over the chips, operations and
    gaps of the busiest chip."""
    per_chip = device_lines(planes)
    used = sorted(per_chip)[:chips]
    if not used:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "events": {}}
    reduced = {c: reduce_device(per_chip[c]) for c in used}
    busiest = max(used, key=lambda c: reduced[c]["busy_s"])
    return {
        "busy_s": sum(r["busy_s"] for r in reduced.values()) / len(used),
        "window_s": sum(r["window_s"] for r in reduced.values()) / len(used),
        "device_ops": reduced[busiest]["ops"],
        "idle_gaps": reduced[busiest]["gaps"],
        "events": {c: per_chip[c] for c in used},
    }


def describe(planes: dict, names: int = 12) -> dict:
    """A summary of a trace for reading by hand: planes, lines, counts and
    the names that take most time on each line."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {}
        for lname, events in lines.items():
            per = {}
            for name, _, d in events:
                per[name] = per.get(name, 0.0) + d
            topn = sorted(per.items(), key=lambda kv: -kv[1])[:names]
            out[pname][lname] = {
                "events": len(events),
                "top": [[n[:120], round(d / 1e9, 6)] for n, d in topn]}
    return out


class WindowTracer:
    """One `jax.profiler` trace of `length` seconds starting `start_at`
    seconds into the window, driven by `tick(elapsed)` from whichever
    thread watches the window's clock. Only the device and the host's XLA
    runtime are traced: the Python tracer would slow the host threads the
    serving cells measure. With `directory=None` (`--trace 0`) every call
    is a no-op and `state` stays "off"."""

    def __init__(self, directory, start_at: float = 0.0, length: float = 0.0):
        self.directory = directory
        self.start_at, self.stop_at = start_at, start_at + length
        self.state = "pending" if directory else "off"
        self.overhead_s = 0.0  # time spent starting and stopping the profiler
        self._reduced = None

    def tick(self, elapsed: float) -> None:
        import jax

        if self.state == "pending" and elapsed >= self.start_at:
            t = time.perf_counter()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self.state = "running"
            self.overhead_s += time.perf_counter() - t
        elif self.state == "running" and elapsed >= self.stop_at:
            self.finish()

    def finish(self) -> None:
        import jax

        if self.state == "running":
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.overhead_s += time.perf_counter() - t
            self.state = "done"
        elif self.state == "pending":
            self.state = "off"  # the window ended before the trace began

    def reduced(self, chips: int):
        """The reduced trace, or None when none was taken."""
        if self.state != "done":
            return None
        if self._reduced is None:
            self._reduced = reduce_trace(
                load(find_xplane(self.directory)), chips)
        return self._reduced


def for_window(args, traffic: dict) -> WindowTracer:
    """The window's tracer: with `--trace 1`, `trace_seconds` from the
    middle of the window; with `--trace 0`, one that does nothing."""
    if not args.trace:
        return WindowTracer(None)
    length = min(float(traffic.get("trace_seconds", 4.0)), args.seconds / 2)
    return WindowTracer(args.trace_dir, (args.seconds - length) / 2, length)


def idle_share_percent(context):
    """Share of the traced span in which no operation ran on the device, in
    percent (what every `device_idle_share.*` reader returns)."""
    reduced = context["tracer"].reduced(context["cell"].chips)
    if not reduced or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def time_share_percent(context, pattern: str):
    """Share of the device's busy time in operations whose name matches
    `pattern`, averaged over the chips used, in percent."""
    reduced = context["tracer"].reduced(context["cell"].chips)
    if not reduced or not reduced["busy_s"]:
        return None
    seconds = [time_matching(events, pattern)
               for events in reduced["events"].values()]
    return 100.0 * (sum(seconds) / len(seconds)) / reduced["busy_s"]


if __name__ == "__main__":
    # `python3 benchmark/harness/trace_reduce.py <trace dir>`: a trace's
    # planes, lines and heaviest names, for reading by hand.
    import json
    import sys

    print(json.dumps(describe(load(find_xplane(sys.argv[1]))), indent=1))
