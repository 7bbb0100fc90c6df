"""Kernel registry: one dispatch seam for accelerated (Pallas) kernels.

The reference framework discovers per-backend "helper" implementations
(`ConvolutionHelper`/`LSTMHelper`, PAPER.md layer 1) with a portable
fallback when no accelerated helper applies. This module is the JAX
port's equivalent: each kernel name maps to an ORDERED list of candidate
implementations, each declaring `is_available(backend, shapes, dtypes)`,
and `resolve()` picks the first available one — memoized per
(kernel, mode, backend, signature) so the probe runs once per distinct
jit signature, not once per dispatch (the superstep block-restack path
calls into the seam for every block; a memo hit must not re-probe).

Selection is part of the PROGRAM IDENTITY: `nn/jit_cache.py` folds
`config_key()` into every cache key and `compilation/store.py` folds
`config_fingerprint()` into the AOT fingerprint document, so flipping a
kernel knob can never serve a stale cached program or executable. The
fingerprint also carries `SELECTION_RULES`, because a stored executable
outlives the code that chose its kernels: the same mode under other rules
is another program.

Env knobs (read at resolve time, so tests can monkeypatch):

- ``DL4J_TPU_KERNELS=auto|xla|pallas`` — global mode. ``auto`` (default)
  picks the first candidate whose availability probe passes — Pallas on
  TPU when the shape/dtype/activation constraints hold, the bit-stable
  XLA fallback otherwise. ``xla`` forces the fallback everywhere (the CI
  default contract: bit-identical to the pre-registry inline code).
  ``pallas`` forces the Pallas candidate where structurally possible
  (interpret mode off-TPU — numerics float-close, speed irrelevant;
  parity tests run this way on the CPU mesh).
- ``DL4J_TPU_KERNEL_<NAME>`` (e.g. ``DL4J_TPU_KERNEL_LSTM_CELL``) —
  per-kernel override, same values, wins over the global mode.

``python -m deeplearning4j_tpu.kernels`` prints what resolves and why, for
every name in `KERNEL_MODULES`: `bottleneck_block`, `lstm_cell`,
`fused_update`, `norm_act`, `flash_attention` and its `_paged`, `masked_`,
`banded_` and `latent_` siblings, `grouped_matmul` (the dropless
experts' grouped products) and `rotary` (the rotate-half rotary
embedding, one pass each way over the operand).

Registration is lazy: kernel modules self-register at import, and
`resolve()`/`describe()` import them on demand, so importing the
registry (which every jit-cache key construction does) stays cheap.
"""

from __future__ import annotations

import importlib
import os
import threading
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

from deeplearning4j_tpu import observability as _obs

MODES = ("auto", "xla", "pallas")

# Version of the rules that turn a mode into an implementation, for
# `config_fingerprint()`. Bump it whenever an `is_available` rule changes
# which body a signature gets, so that no AOT artifact written under the
# old rules is loaded for the new program. 1 (implicit, no key): PR 10-24. 2: `norm_act` refuses
# the Pallas body for BatchNorm under `auto` (PR 25). 3: `masked_attention`
# exists, and on a TPU `nn/layers/dsa.py`'s attention resolves its Pallas
# body where it ran XLA row blocks (PR 27). 4: `fused_update` refuses the
# Pallas body under `auto` for a dispatch with a leaf of a grid block or
# more, so a step with large layers no longer ravels them (PR 29). 5:
# `banded_attention` exists, and an extended attention layer without an
# indexer resolves it where it resolved `masked_attention` (PR 30).
# `latent_attention` (PR 32) is a new name that no older signature resolves:
# no bump. 6: `grouped_matmul` exists, and on a TPU the dropless experts'
# grouped products resolve its Pallas body where they were
# `jax.lax.ragged_dot` calls outside the registry (PR 35).
# 7: `rotary` exists, and on a TPU `dsa.rope` resolves its Pallas body
# where it ran the XLA expression inline. 8: on a TPU a grouped product
# whose whole `[K, N]` table outgrows VMEM beside a row tile resolves
# `grouped_matmul`'s Pallas body, over parts of N, where it ran
# `jax.lax.ragged_dot`.
SELECTION_RULES = 8

# Meta key the registry itself adds to a signature traced under a mesh of
# more than one device (and `--probe --meta mesh_devices=N` passes by hand).
MESH_DEVICES = "mesh_devices"

# Kernel name -> module that registers its candidates at import.
KERNEL_MODULES = {
    # Fused ResNet bottleneck chain (PR 19): conv1x1/BN/act x3 + residual
    # in one VMEM residency; XLA fallback is the unfused vertex chain.
    "bottleneck_block": "deeplearning4j_tpu.kernels.bottleneck_block",
    "lstm_cell": "deeplearning4j_tpu.kernels.lstm_cell",
    "fused_update": "deeplearning4j_tpu.kernels.fused_update",
    "norm_act": "deeplearning4j_tpu.kernels.norm_act",
    "flash_attention": "deeplearning4j_tpu.kernels.flash_attention",
    # Paged decode-attention gather variant (PR 15): registered by the
    # same module; auto off-TPU resolves to the XLA dense-gather
    # composite, which is bit-identical to the dense stepper.
    "flash_attention_paged": "deeplearning4j_tpu.kernels.flash_attention",
    # Grouped-query attention under a per-query key mask (PR 27), the
    # sparse-attention layer's `dsa.attend`: same module again; auto
    # off-TPU resolves the XLA row blocks of `nn/layers/dsa.py`.
    "masked_attention": "deeplearning4j_tpu.kernels.flash_attention",
    # The same kernels with no mask operand (PR 30): a causal, windowed or
    # bidirectional layer without an indexer (`attn.full`, `attn.sliding`);
    # the tile's mask comes from iotas and only tiles that meet the band
    # are visited.
    "banded_attention": "deeplearning4j_tpu.kernels.flash_attention",
    # The same kernels once more with a second, narrower product added into
    # the score tile, its key tile shared by all heads: multi-head latent
    # attention's core (`mla.attend`).
    "latent_attention": "deeplearning4j_tpu.kernels.flash_attention",
    # The dropless experts' grouped products over rows sorted by expert
    # (PR 35): rows by group against a table, against its transpose, and a
    # table's gradient; XLA's candidate is `jax.lax.ragged_dot`.
    "grouped_matmul": "deeplearning4j_tpu.kernels.grouped_matmul",
    # The rotate-half rotary embedding: one pass over the operand's
    # positions-minor view `[heads * D, S]` forward and one backward (the
    # rotation by the opposite angle); XLA's candidate is
    # `nn/layers/dsa.py::rope_xla`.
    "rotary": "deeplearning4j_tpu.kernels.rotary",
}


class KernelImpl(NamedTuple):
    """One candidate implementation of a kernel.

    `is_available(backend, shapes, dtypes, meta=(), forced=False)`
    returns `(ok, reason)`. `forced` relaxes backend/tiling requirements
    that Pallas interpret mode does not need (a forced impl must still
    refuse structurally impossible cases, e.g. an activation the kernel
    cannot express — resolution then falls back with the reason in the
    `Resolution`)."""

    name: str
    is_available: Callable[..., Tuple[bool, str]]


class Resolution(NamedTuple):
    kernel: str
    impl: str
    reason: str


_REGISTRY: dict = {}
_MEMO: dict = {}
_LOCK = threading.Lock()
_PROBES = 0  # is_available invocations, for the hoisting counter assertion

# Per-JX008 convention: family at import, children cached, `.inc()` in the
# (trace-time) dispatch path.
_M_DISPATCH = _obs.metrics.counter(
    "dl4j_kernel_dispatch_total",
    "kernel dispatch-seam resolutions by kernel name and resolved impl",
    label_names=("kernel", "impl"))
_DISPATCH_CHILDREN: dict = {}


def register(kernel: str, impls: Sequence[KernelImpl]) -> None:
    """Register the ordered candidate list for `kernel` (first available
    wins in `auto` mode). Re-registration replaces — module reload safe."""
    _REGISTRY[kernel] = tuple(impls)


def _ensure(kernel: str) -> None:
    if kernel not in _REGISTRY:
        mod = KERNEL_MODULES.get(kernel)
        if mod is None:
            raise KeyError(f"unknown kernel {kernel!r}; known: "
                           f"{sorted(KERNEL_MODULES)}")
        importlib.import_module(mod)  # self-registers


def kernel_names() -> Tuple[str, ...]:
    return tuple(sorted(KERNEL_MODULES))


def mode_for(kernel: str) -> Tuple[str, str]:
    """(mode, source) for one kernel: the per-kernel env override if set,
    else the global `DL4J_TPU_KERNELS`, else `auto`."""
    per = os.environ.get("DL4J_TPU_KERNEL_" + kernel.upper())
    if per:
        if per not in MODES:
            raise ValueError(
                f"DL4J_TPU_KERNEL_{kernel.upper()}={per!r}: want one of {MODES}")
        return per, "DL4J_TPU_KERNEL_" + kernel.upper()
    glob = os.environ.get("DL4J_TPU_KERNELS")
    if glob:
        if glob not in MODES:
            raise ValueError(
                f"DL4J_TPU_KERNELS={glob!r}: want one of {MODES}")
        return glob, "DL4J_TPU_KERNELS"
    return "auto", "default"


def config_key() -> Tuple:
    """The kernel-selection identity of the process env: folded into every
    jit-cache key (`nn/jit_cache.py`) so a knob flip can never reuse a
    program traced under a different selection."""
    return tuple((k, mode_for(k)[0]) for k in kernel_names())


def config_fingerprint() -> dict:
    """JSON-able form of `config_key()` for the AOT fingerprint document
    (`compilation/store.py::build_fingerprint_doc`), with the version of
    the rules that turn a mode into an implementation: the jit cache dies
    with the process, a stored executable does not."""
    return {**{k: mode_for(k)[0] for k in kernel_names()},
            "selection_rules": SELECTION_RULES}


def probe_count() -> int:
    """Total `is_available` probe invocations this process — the hoisting
    contract (tests): repeated same-signature blocks add ZERO probes."""
    return _PROBES


def resolved() -> Tuple[Resolution, ...]:
    """Every resolution this process has made at a real call signature
    (one per distinct signature): which implementation its programs were
    traced with, and why — what `chip_smoke.py` prints beside each step."""
    with _LOCK:
        return tuple(res for key, res in _MEMO.items() if key[3])


def clear_cache() -> None:
    """Drop the resolution memo (tests flip env knobs between asserts)."""
    with _LOCK:
        _MEMO.clear()


def _probe(impl: KernelImpl, backend, shapes, dtypes, meta, forced) -> Tuple[bool, str]:
    global _PROBES
    _PROBES += 1
    return _available(impl, backend, shapes, dtypes, meta, forced)


def _available(impl: KernelImpl, backend, shapes, dtypes, meta,
               forced) -> Tuple[bool, str]:
    """`impl.is_available`, after the one rule that holds for every Pallas
    body on a TPU: inside a program that GSPMD partitions over several
    devices the chip's compiler refuses it outright, whatever the shape."""
    n = dict(meta).get(MESH_DEVICES, 1)
    if impl.name == "pallas" and backend == "tpu" and n > 1:
        return False, (f"the program is partitioned over a {n}-device mesh "
                       "and the TPU compiler refuses a Pallas body there "
                       "(\"Mosaic kernels cannot be automatically "
                       "partitioned. Please wrap the call in a shard_map.\")")
    return impl.is_available(backend, shapes, dtypes, meta=meta,
                             forced=forced)


def _mesh_devices() -> int:
    """Devices of the mesh the program being traced is partitioned over: the
    active `ParallelContext`'s (wrappers and steppers install it around
    every sharded dispatch), 1 without one."""
    from deeplearning4j_tpu.parallel.context import current_context

    ctx = current_context()
    return 1 if ctx is None else int(ctx.mesh.devices.size)


def _count_dispatch(kernel: str, impl: str) -> None:
    child = _DISPATCH_CHILDREN.get((kernel, impl))
    if child is None:
        child = _DISPATCH_CHILDREN.setdefault(
            (kernel, impl), _M_DISPATCH.labels(kernel=kernel, impl=impl))
    child.inc()


def _default_backend() -> str:
    import jax

    return jax.default_backend()


def interpret_mode() -> bool:
    """Whether a Pallas body that resolved must run in interpret mode: off
    the TPU there is no Mosaic compiler. The one place the kernels ask —
    on a TPU this is False, so a body the chip's compiler refuses fails
    its compile instead of running somewhere else."""
    return _default_backend() != "tpu"


def resolve(kernel: str, *, backend: Optional[str] = None,
            shapes: Tuple = (), dtypes: Tuple = (), meta: Tuple = ()) -> Resolution:
    """Pick the implementation for `kernel` under the current env mode.

    `shapes`/`dtypes`/`meta` are hashable tuples describing the call
    signature (layer dims, leaf dtypes, activation names, ...); they key
    the memo together with (kernel, mode, backend), so resolution — and
    its `is_available` probes — runs once per distinct jit signature.
    Called at trace time only; the result feeds static Python dispatch,
    never a traced value."""
    if backend is None:
        backend = _default_backend()
    _ensure(kernel)
    mode, source = mode_for(kernel)
    n = _mesh_devices()
    if n > 1:
        meta = tuple(meta) + ((MESH_DEVICES, n),)
    key = (kernel, mode, backend, shapes, dtypes, meta)
    with _LOCK:
        res = _MEMO.get(key)
    if res is None:
        res = _resolve_uncached(kernel, mode, source, backend, shapes,
                                dtypes, meta)
        with _LOCK:
            res = _MEMO.setdefault(key, res)
    _count_dispatch(kernel, res.impl)
    return res


def _resolve_uncached(kernel, mode, source, backend, shapes, dtypes,
                      meta) -> Resolution:
    candidates = _REGISTRY[kernel]
    note = ""
    if mode != "auto":
        forced = next((c for c in candidates if c.name == mode), None)
        if forced is not None:
            ok, reason = _probe(forced, backend, shapes, dtypes, meta,
                                forced=True)
            if ok:
                return Resolution(kernel, mode,
                                  f"forced via {source}: {reason}")
            note = f"{mode} forced via {source} but unavailable ({reason}); "
        else:
            note = f"{mode} forced via {source} but not a candidate; "
    last = None
    refused = ""
    for c in candidates:
        ok, reason = _probe(c, backend, shapes, dtypes, meta, forced=False)
        # The winner's reason carries why each earlier candidate said no:
        # "xla" alone does not say that it was the registry's decision.
        last = Resolution(kernel, c.name, note + reason + refused)
        if ok:
            return last
        refused += f" [{c.name} unavailable: {reason}]"
    # No candidate available (should not happen: every kernel registers an
    # unconditional XLA fallback) — surface the last probe's reason.
    return last


def probe(kernel: str, *, backend: Optional[str] = None, shapes: Tuple = (),
          dtypes: Tuple = (), meta: Tuple = ()):
    """Dry-run every candidate of `kernel` at a hypothetical signature —
    the ``--probe`` CLI's payload for debugging forced-kernel rollouts.

    Unlike `resolve()` this is NOT memoized and probes ALL candidates
    (each with `forced=True` when the active mode names it, mirroring
    `_resolve_uncached`'s semantics), so the report shows the refusal
    reason per candidate, not just the winner. No jit, no trace — pure
    availability checks. Returns ``(selected_impl, rows)``."""
    if backend is None:
        backend = _default_backend()
    _ensure(kernel)
    mode, source = mode_for(kernel)
    rows = []
    for c in _REGISTRY[kernel]:
        forced = mode == c.name
        ok, reason = _available(c, backend, shapes, dtypes, meta, forced)
        rows.append({"impl": c.name, "available": bool(ok),
                     "forced": forced, "reason": reason})
    selected = None
    if mode != "auto":
        selected = next((r["impl"] for r in rows
                         if r["impl"] == mode and r["available"]), None)
    if selected is None:
        selected = next((r["impl"] for r in rows if r["available"]),
                        rows[-1]["impl"] if rows else None)
    return selected, rows


def describe(backend: Optional[str] = None):
    """Resolution table for every registered kernel at a generic (shapeless)
    signature — the CLI's payload and the smoke tests' hook."""
    rows = []
    for name in kernel_names():
        mode, source = mode_for(name)
        res = resolve(name, backend=backend)
        rows.append({"kernel": name, "mode": mode, "mode_source": source,
                     "impl": res.impl, "reason": res.reason})
    return rows
