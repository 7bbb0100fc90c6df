"""Per-program HBM accounting + live-buffer attribution.

Answers "where did the HBM go" with two complementary views:

- **Static, per program**: every executable that materializes in
  `compilation/program.py` (AOT-store hit, live compile, or the
  profiler's cost-analysis probe) reports `compiled.memory_analysis()`
  — XLA's own accounting of argument / output / temp / generated-code
  bytes — into the `dl4j_program_hbm_bytes{program,kind}` gauges. This
  is the number that explains an OOM *before* it happens: temp bytes are
  the scratch high-water mark the program will ask the allocator for.
- **Dynamic, per owner**: `live_buffer_report()` walks
  `jax.live_arrays()` and attributes every buffer to a registered model
  tree (params / state / opt_state, grouped by top-level leaf prefix,
  e.g. `layer_3`), with the remainder reported as unattributed. Models
  register via `register_tree(name, net)` (the serving host and
  `StepProfiler` do this automatically); registration holds only a
  weakref, so it never extends a model's lifetime.

`measured_model_bytes(net)` combines both for the serving tier: the
summed bytes of the net's *actual device-resident* array leaves plus the
largest transient (temp + output) footprint recorded for one of its
programs — the measured eviction cost `serving/host.py` budgets with
(falling back to the leaf-`nbytes` estimate when nothing device-resident
exists yet).

Everything here runs at compile time or scrape time — never in the
training hot loop.
"""

from __future__ import annotations

import threading
import weakref
from typing import Any, Dict, Optional

from deeplearning4j_tpu import observability as _obs
from deeplearning4j_tpu.analysis.locktrace import named_lock

# Byte categories reported by XLA's CompiledMemoryStats -> gauge `kind`.
_STAT_KINDS = (
    ("argument", "argument_size_in_bytes"),
    ("output", "output_size_in_bytes"),
    ("temp", "temp_size_in_bytes"),
    ("generated_code", "generated_code_size_in_bytes"),
    ("alias", "alias_size_in_bytes"),
)

_M_PROGRAM_HBM = _obs.metrics.gauge(
    "dl4j_program_hbm_bytes",
    "Static per-program device memory from XLA's memory_analysis(): "
    "argument/output/temp/generated_code/alias bytes plus their total "
    "(aliased bytes counted once)",
    label_names=("program", "kind"))

_lock = named_lock("observability.memory")
_programs: Dict[str, Dict[str, Any]] = {}   # label -> {bytes, net_ref}
_trees: Dict[str, Any] = {}                 # name -> weakref to a net


def program_label(kind: str, static: Optional[dict] = None) -> str:
    """Stable `program` label for a compiled executable: the program kind
    plus its static config, e.g. `solver_step[algo=LBFGS]`."""
    if not static:
        return kind
    inner = ",".join(f"{k}={static[k]}" for k in sorted(static))
    return f"{kind}[{inner}]"


def record_program_memory(program: str, compiled, net=None) -> Optional[dict]:
    """Capture `compiled.memory_analysis()` into the per-program gauges.
    Safe on every backend: returns the byte dict, or None when the
    executable does not expose memory stats. Never raises."""
    try:
        analysis = compiled.memory_analysis()
        if analysis is None:
            return None
        stats = {name: int(getattr(analysis, attr, 0) or 0)
                 for name, attr in _STAT_KINDS}
    except Exception:
        return None
    stats["total"] = max(0, stats["argument"] + stats["output"]
                         + stats["temp"] + stats["generated_code"]
                         - stats["alias"])
    for kind, v in stats.items():
        _M_PROGRAM_HBM.labels(program=program, kind=kind).set(v)
    with _lock:
        _programs[program] = {
            "bytes": stats,
            "net_ref": None if net is None else weakref.ref(net),
        }
    return stats


def program_memory_snapshot() -> Dict[str, Dict[str, int]]:
    """{program: {kind: bytes}} for every recorded executable."""
    with _lock:
        return {label: dict(rec["bytes"]) for label, rec in _programs.items()}


# --------------------------------------------------- live-buffer attribution


def register_tree(name: str, net) -> None:
    """Register a model for live-buffer attribution (weakref only)."""
    with _lock:
        _trees[str(name)] = weakref.ref(net)


def unregister_tree(name: str) -> None:
    with _lock:
        _trees.pop(str(name), None)


def _leaf_prefix(path) -> str:
    if not path:
        return "_"
    entry = path[0]
    for attr in ("key", "name", "idx"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    return str(entry)


def _owned_leaves(net):
    """(leaf, group) pairs for a net's device-facing trees, where group is
    `attr/top-level-prefix` (e.g. `params_tree/layer_0`)."""
    import jax

    for attr in ("params_tree", "state", "opt_state"):
        tree = getattr(net, attr, None)
        if tree is None:
            continue
        try:
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        except Exception:
            continue
        for path, leaf in flat:
            if hasattr(leaf, "nbytes"):
                yield leaf, f"{attr}/{_leaf_prefix(path)}"


def live_buffer_report() -> Dict[str, Any]:
    """Attribute `jax.live_arrays()` bytes to registered model trees,
    grouped per model by param-leaf prefix. Buffers owned by nothing
    registered land in `unattributed_bytes`."""
    from deeplearning4j_tpu.observability.metrics import backend_is_up

    if not backend_is_up():  # never bring a backend up just to report zero
        return {"total_bytes": 0, "models": {}, "unattributed_bytes": 0}
    import jax

    owners: Dict[int, tuple] = {}
    with _lock:
        registered = list(_trees.items())
    for name, ref in registered:
        net = ref()
        if net is None:
            unregister_tree(name)
            continue
        for leaf, group in _owned_leaves(net):
            owners[id(leaf)] = (name, group)

    models: Dict[str, Dict[str, Any]] = {}
    total = unattributed = 0
    for a in jax.live_arrays():
        nb = int(getattr(a, "nbytes", 0) or 0)
        total += nb
        who = owners.get(id(a))
        if who is None:
            unattributed += nb
            continue
        name, group = who
        m = models.setdefault(name, {"bytes": 0, "groups": {}})
        m["bytes"] += nb
        m["groups"][group] = m["groups"].get(group, 0) + nb
    return {"total_bytes": total, "models": models,
            "unattributed_bytes": unattributed}


# ------------------------------------------------------- serving integration


def measured_model_bytes(net) -> Optional[int]:
    """Measured device footprint of a loaded model: summed bytes of its
    jax.Array leaves (the buffers actually committed to the device, not a
    host-side nbytes guess) plus the largest transient temp+output
    footprint among this net's recorded programs. None when the net holds
    no device arrays yet — callers keep the estimate."""
    try:
        import jax
    except Exception:
        return None
    total = 0
    found = False
    for attr in ("params_tree", "state", "opt_state"):
        tree = getattr(net, attr, None)
        if tree is None:
            continue
        for leaf in jax.tree_util.tree_leaves(tree):
            if isinstance(leaf, jax.Array):
                total += int(getattr(leaf, "nbytes", 0) or 0)
                found = True
    if not found:
        return None
    transient = 0
    with _lock:
        for rec in _programs.values():
            ref = rec.get("net_ref")
            if ref is not None and ref() is net:
                b = rec["bytes"]
                transient = max(transient,
                                b.get("temp", 0) + b.get("output", 0))
    return total + transient


def report() -> Dict[str, Any]:
    """The `/api/memory` payload: static per-program accounting + live
    attribution in one document."""
    return {"programs": program_memory_snapshot(),
            "live": live_buffer_report()}
