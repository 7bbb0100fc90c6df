"""A traced train step by scope: the busy time of the device, each instant
once, put down to the vertex or the phase of the step that issued it.

Since PR 36 the engine runs every vertex under `L.<vertex>` and every phase
of the train step under `step.<phase>` (`deeplearning4j_tpu/nn/engine.py::
scope`; inside `step.update` a layer's updater runs under `L.<key>` again),
and the layer bodies open dotted scopes of their own inside (`dsa.attend`,
`moe.experts`, `lm.head`, ...). The names are in the compiled program's text
and not in the trace; `scope_time.op_names` joins the two by instruction
name. This module builds on that join:

- **A row** is (the engine's scopes of an `op_name`, joined by `/`; `fwd`, or
  `bwd` where the `op_name` holds `transpose(`; the innermost other dotted
  scope, if any). An `op_name` with no engine scope is the row `unscoped`.
  No name of a model, a layer or a scope is written here: an engine scope is
  a piece `L.<word>` or `step.<word>`, any other piece of lower-case words
  joined by dots is a layer body's.
- **An instruction whose line has no `op_name`** (the copies, relayouts and
  converts XLA materialises around a custom call or a fusion) takes the
  `op_name` of the instruction that consumes it, else of its first operand's
  producer, through further nameless instructions, both read from the same
  text. What still has none is the row `unnamed`; an event whose instruction
  the text does not hold is `unmatched`.
- **Each instant once**: a `while` is an event and so is every operation of
  its body, so an instant belongs to the innermost event open at it; the
  rows sum to the busy time.
- **Ms a step**: the steps of the trace are counted as `kernel_costs.
  scoped_seconds_and_steps` counts them (the scoped instruction that takes
  most time runs once a step).

`table(context)` returns None where there is no trace, no program text or no
engine scope in it (a tree from before PR 36), and else the table; it is
built once a run, kept in the context, and written beside the trace as
`scope_table.json`. `python3 benchmark/harness/scope_table.py
.bench_trace/<cell>` prints that file for reading by hand.
"""

from __future__ import annotations

import json
import os
import re
import time

FILE = "scope_table.json"
UNSCOPED, UNNAMED, UNMATCHED = "unscoped", "unnamed", "unmatched"
_ENGINE = re.compile(r"^(?:L|step)\.[\w\-]+$")
_DOTTED = re.compile(r"^[a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+$")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9_\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_WALK = 64  # nameless instructions followed before giving up


def _operands(rest: str, start: int) -> list:
    """Names between the parenthesis at `start` and the one that closes it:
    each operand's last word (a text may print an operand's shape before
    its name, and a shape holds commas and parentheses of its own)."""
    depth, piece, pieces = 0, [], []
    for c in rest[start:]:
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                break
        if depth == 1 and c == ",":
            pieces.append("".join(piece))
            piece = []
        elif depth > 1 or c != "(":
            piece.append(c)
    pieces.append("".join(piece))
    return [p.split()[-1].lstrip("%") for p in pieces if p.strip()]


def instructions(text: str) -> dict:
    """`{instruction: (op_name or "", [operand names])}` over the text of
    an HLO module, every computation of it. An `op_name` that is no path
    through a traced function (no `jit(...)` or other transform in it) is
    an argument's own name (`params['attn0']['Wq']`, which a copy of the
    argument inherits) or a reducer's (`reduce_sum`): it says nothing of
    where the instruction was issued and counts as none."""
    out = {}
    for line in text.splitlines():
        head = _HEAD.match(line)
        if not head:
            continue
        rest = head.group(2)
        opcode = _OPCODE.search(rest)
        if not opcode:
            continue
        named = _OP_NAME.search(rest, opcode.end())
        op_name = named.group(1) if named and "(" in named.group(1) else ""
        out[head.group(1)] = (op_name, _operands(rest, opcode.end() - 1))
    return out


class Names:
    """`op_name` by instruction, with the inheritance of the module's
    docstring for an instruction whose own line has none."""

    def __init__(self, parsed: dict):
        self.parsed = parsed
        self.consumers = {}
        for name, (_, operands) in parsed.items():
            for operand in operands:
                self.consumers.setdefault(operand, []).append(name)
        self._found = {}

    def _down(self, name: str) -> str:
        """The first `op_name` among the consumers, breadth first through
        nameless ones."""
        queue, seen = list(self.consumers.get(name, ())), {name}
        for _ in range(_WALK):
            if not queue:
                break
            user = queue.pop(0)
            if user in seen:
                continue
            seen.add(user)
            if self.parsed[user][0]:
                return self.parsed[user][0]
            queue.extend(self.consumers.get(user, ()))
        return ""

    def _up(self, name: str) -> str:
        """The `op_name` of the first operand's producer, through nameless
        producers."""
        for _ in range(_WALK):
            operands = self.parsed.get(name, ("", ()))[1]
            if not operands or operands[0] not in self.parsed:
                return ""
            name = operands[0]
            if self.parsed[name][0]:
                return self.parsed[name][0]
        return ""

    def of(self, name: str):
        """An instruction's `op_name`, its own or inherited; "" where no
        walk finds one; None where the text does not hold the instruction."""
        if name not in self.parsed:
            return None
        if name not in self._found:
            self._found[name] = (self.parsed[name][0] or self._down(name)
                                 or self._up(name))
        return self._found[name]


def row_of(op_name) -> tuple:
    """`(scope, side, inner)` of an `op_name` (None: `unmatched`)."""
    if not op_name:
        return (UNMATCHED if op_name is None else UNNAMED, "", "")
    pieces = re.split(r"[/()]", op_name)
    # once each: a hand-written backward pass can carry its vertex twice
    engine = list(dict.fromkeys(p for p in pieces if _ENGINE.match(p)))
    inner = [p for p in pieces if _DOTTED.match(p) and not _ENGINE.match(p)]
    return ("/".join(engine) or UNSCOPED,
            "bwd" if "transpose(" in op_name else "fwd",
            inner[-1] if inner else "")


def self_times(events) -> dict:
    """`{event name: ns}`: every instant in which some event is open, put
    down to the event that opened last (the innermost, where events nest as
    a loop's and its body's do). The values sum to the union of the events'
    intervals."""
    out = {}
    stack = []  # (end, name), in the order opened
    now = None

    def run_to(limit):
        nonlocal now
        while stack and now < limit:
            end, name = stack[-1]
            if end <= now:
                stack.pop()
                continue
            upto = min(end, limit)
            out[name] = out.get(name, 0.0) + (upto - now)
            now = upto
        now = max(now, limit)

    for start, end, name in sorted(
            ((s, s + d, n) for n, s, d in events if d > 0),
            key=lambda e: (e[0], -e[1])):
        if now is None:
            now = start
        run_to(start)
        stack.append((end, name))
    if stack:
        run_to(max(end for end, _ in stack))
    return out


def build(events_by_chip: dict, names: Names, steps: int) -> dict:
    """The table of `events_by_chip` (`{chip: [(name, start_ns,
    duration_ns)]}`), ms a step averaged over the chips."""
    per = 1e6 * steps * len(events_by_chip)  # ns over the chips -> ms a step
    rows, ops = {}, {}
    for events in events_by_chip.values():
        for name, ns in self_times(events).items():
            key = name.split(" ", 1)[0]
            row = row_of(names.of(key))
            rows[row] = rows.get(row, 0.0) + ns / per
            ops[key] = ops.get(key, 0.0) + ns / per
    busy = sum(rows.values())
    outside = sum(ms for (scope, _, _), ms in rows.items()
                  if scope in (UNSCOPED, UNNAMED, UNMATCHED))
    phases = sum(ms for (scope, _, _), ms in rows.items()
                 if scope.startswith("step."))
    heaviest = sorted(ops.items(), key=lambda kv: -kv[1])

    def described(key, ms):
        return [key, ms, *row_of(names.of(key)), names.of(key) or ""]

    return {
        "steps": steps, "busy_ms_a_step": busy,
        "unscoped_percent": 100.0 * outside / busy if busy else None,
        "updater_percent": 100.0 * phases / busy if busy else None,
        "rows": sorted(([*row, ms] for row, ms in rows.items()),
                       key=lambda r: -r[3]),
        # the heaviest instructions, and the heaviest of those no engine
        # scope holds: what a reader asks of a name like `fusion.742`
        "ops": [described(k, ms) for k, ms in heaviest[:60]],
        "left_over": [described(k, ms) for k, ms in heaviest
                      if row_of(names.of(k))[0] in
                      (UNSCOPED, UNNAMED, UNMATCHED)][:30],
    }


def table(context):
    """The run's table (module docstring), or None; built once a run."""
    if "scope_table" not in context:
        context["scope_table"] = _table(context)
    return context["scope_table"]


def _table(context):
    from benchmark.harness import kernel_costs

    t0 = time.perf_counter()
    counted = kernel_costs.scoped_seconds_and_steps(context, ("L.", "step."))
    if not counted or not counted[1]:
        return None
    parsed = {}
    for exe in context.get("executables") or []:
        try:
            text = exe.as_text()
        except Exception:  # a plain jit callable has no text
            continue
        parsed.update(instructions(text or ""))
    reduced = context["tracer"].reduced(context["cell"].chips)
    out = build(reduced["events"], Names(parsed), counted[1])
    out["cell"] = getattr(context["cell"], "name", None)
    out["seconds_to_build"] = time.perf_counter() - t0
    directory = getattr(context["tracer"], "directory", None)
    if directory and os.path.isdir(directory):
        with open(os.path.join(directory, FILE), "w") as f:
            json.dump(out, f)
    return out


def printed(doc: dict, top: int = 25) -> str:
    """The table as text: a line a scope (forward | backward), its inner
    scopes under it, then the heaviest instructions with their rows."""
    by_scope = {}
    for scope, side, inner, ms in doc["rows"]:
        entry = by_scope.setdefault(scope, {"fwd": 0.0, "bwd": 0.0, "": 0.0,
                                            "inner": {}})
        entry[side] += ms
        if inner:
            pair = entry["inner"].setdefault(inner, {"fwd": 0.0, "bwd": 0.0})
            pair[side or "fwd"] += ms
    lines = [f"{doc.get('cell')}: {doc['steps']} steps of "
             f"{doc['busy_ms_a_step']:.2f} ms busy; outside every engine "
             f"scope {doc['unscoped_percent']:.2f}%, under step.* "
             f"{doc['updater_percent']:.2f}%; built in "
             f"{doc.get('seconds_to_build', 0.0):.1f} s",
             f"  {'scope':44s} {'ms a step':>10s} {'fwd':>9s} {'bwd':>9s}"]

    def total(entry):
        return entry["fwd"] + entry["bwd"] + entry[""]

    for scope, entry in sorted(by_scope.items(), key=lambda kv: -total(kv[1])):
        lines.append(f"  {scope:44s} {total(entry):10.3f} "
                     f"{entry['fwd'] + entry['']:9.3f} {entry['bwd']:9.3f}")
        for inner, pair in sorted(entry["inner"].items(),
                                  key=lambda kv: -sum(kv[1].values())):
            lines.append(f"      {inner:40s} {sum(pair.values()):10.3f} "
                         f"{pair['fwd']:9.3f} {pair['bwd']:9.3f}")
    for title, key in (("heaviest instructions", "ops"),
                       ("heaviest outside every engine scope", "left_over")):
        lines.append(f"  {title}:")
        for name, ms, scope, side, inner, op_name in doc[key][:top]:
            where = " ".join(p for p in (scope, side, inner) if p)
            lines.append(f"      {name:44s} {ms:9.3f}  {where}  "
                         f"[{op_name.rsplit('/', 1)[-1]}]")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys

    with open(os.path.join(sys.argv[1], FILE)) as f:
        print(printed(json.load(f)))
