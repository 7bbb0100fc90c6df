"""Device time by `jax.named_scope`: which share of the busy time went into
operations traced under a scope such as `dsa.` or `moe.`.

A v5e trace names each `XLA Ops` event by its HLO line up to the operands
(`%fusion.12 = ... fusion(...), kind=kLoop, calls=...`) and carries no
`op_name` (looked at by hand, PERF.md PR 26). The compiled program's own
text does: every instruction line ends in `metadata={op_name="jit(step)/
.../dsa.attend/..." ...}`, the scopes of forward and backward alike
(`transpose(jvp(dsa.attend))`). So the reader joins the two by instruction
name. A fusion counts under the scope its own line names (its root's); a
loop's event spans its body's events, so matching time is the union of the
matching intervals and never a sum.
Where the program has no such scope, as before the PR that added them, or
its executable gives no text, nothing is found and the reader returns None.
"""

from __future__ import annotations

import re

_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def op_names(executables) -> dict:
    """`{instruction name: op_name}` over the executables' HLO text."""
    names = {}
    for exe in executables:
        try:
            text = exe.as_text()
        except Exception:  # a plain jit callable has no text
            continue
        for line in (text or "").splitlines():
            m = _LINE.match(line)
            if m:
                names[m.group(1)] = m.group(2)
    return names


def seconds_in_scope(events, names: dict, needle: str,
                     named: str | None = None) -> float:
    """Seconds of `events` (`(name, start_ns, duration_ns)`, names as
    `trace_reduce.short_name` gives them) whose instruction's `op_name`
    contains `needle`, or whose own name matches the regex `named` (for
    what the compiler names itself whatever scope issued it)."""
    from benchmark.harness import trace_reduce

    rx = re.compile(named) if named else None
    # A `while` or a `conditional` is an event and so is every operation
    # inside it, and one operation can match both ways: the union of the
    # intervals counts each instant once.
    spans = [(start, start + duration) for name, start, duration in events
             if duration > 0
             and (needle in names.get(name.split(" ", 1)[0], "")
                  or (rx is not None and rx.search(name)))]
    return sum(e - s for s, e in trace_reduce.merge(spans)) / 1e9


def scope_share_percent(context, needle: str, named: str | None = None):
    """Share of the device's busy time in operations traced under a scope
    whose name contains `needle` (and, with `named`, in operations whose
    own name matches that regex: the union of both), averaged over the
    chips used, in percent; None where there is no trace, or neither the
    program's text nor the trace holds such an operation."""
    reduced = context["tracer"].reduced(context["cell"].chips)
    if not reduced or not reduced["busy_s"]:
        return None
    names = op_names(context.get("executables") or [])
    seconds = [seconds_in_scope(events, names, needle, named)
               for events in reduced["events"].values()]
    if not any(needle in op for op in names.values()) and not any(seconds):
        return None
    return 100.0 * (sum(seconds) / len(seconds)) / reduced["busy_s"]
