"""Framework-level AOT executable store (tentpole layer 2).

The persistent XLA cache (`cache.py`) only skips the *backend compile*; a
fresh process still pays jaxpr tracing + MLIR lowering for every program.
This store serializes the whole compiled executable
(`jax.experimental.serialize_executable`) keyed by a **fingerprint** of
everything that determines the program:

- the model configuration JSON (layer topology, dtypes, updaters, ...);
- the batch signature (pytree structure + per-leaf shape/dtype/weak-type
  and sharding of every argument);
- jit kind + static args (incl. the superstep ``k``/``scan`` shape);
- the active mesh/sharding from ``context_cache_key()`` (axis roles, mesh
  topology, device ids/kinds/platform);
- jax + jaxlib versions, backend platform + device kind + device count,
  and the x64 flag.

Any field changing changes the hash -> a miss -> live compile + write-back.
A hit deserializes the executable directly: **zero tracing, zero XLA**.
An artifact records the ids of the devices its program was compiled for
and is loaded for exactly those (JAX would otherwise load it for every
device of the backend, and a one-device program then dies at call time on
a several-device host). Loads that fail for any reason (corrupt file,
incompatible jaxlib, a recorded device this process does not have) warn
once and are a miss, decided at load time — the store can only ever cost
a disk read, never correctness.

Writes go through tmp-file + ``os.replace`` so concurrent processes
populating the same directory never expose half-written artifacts.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading
import time
import warnings
from typing import Any, Dict, Optional, Tuple

from deeplearning4j_tpu import observability as _obs

FORMAT_VERSION = 2  # 2: artifacts record their device ids

_M_HITS = _obs.metrics.counter(
    "dl4j_compile_cache_hits_total",
    "Compile-cache hits by layer (aot = framework executable store, "
    "persistent = jax/XLA persistent compilation cache)",
    label_names=("source",))
_M_MISSES = _obs.metrics.counter(
    "dl4j_compile_cache_misses_total",
    "Compile-cache misses by layer (see dl4j_compile_cache_hits_total)",
    label_names=("source",))
_M_SECONDS = _obs.metrics.histogram(
    "dl4j_compile_seconds",
    "Seconds to make one program runnable, by source (trace = full "
    "lowering + backend compile, persistent = XLA cache retrieval, "
    "aot = executable deserialization)",
    label_names=("source",))
_M_HITS_AOT = _M_HITS.labels(source="aot")
_M_MISSES_AOT = _M_MISSES.labels(source="aot")
_M_SECONDS_AOT = _M_SECONDS.labels(source="aot")


def _leaf_desc(leaf) -> Tuple:
    import jax

    try:
        aval = jax.typeof(leaf)
        shape = tuple(int(d) for d in aval.shape)
        dtype = str(aval.dtype)
        weak = bool(getattr(aval, "weak_type", False))
    except TypeError:  # not an array-like leaf: its type is its identity
        shape, dtype, weak = (), str(type(leaf).__name__), False
    sharding = getattr(leaf, "sharding", None)
    return (shape, dtype, weak, None if sharding is None else str(sharding))


def tree_signature(args) -> Dict[str, Any]:
    """JSON-able description of the argument pytree: structure string plus
    per-leaf (shape, dtype, weak_type, sharding). `None` masks live in the
    structure, so a masked batch fingerprints differently from an unmasked
    one — exactly like the programs they trace."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return {
        "tree": str(treedef),
        "leaves": [list(_leaf_desc(leaf)) for leaf in leaves],
    }


def _context_desc(key) -> Optional[Dict[str, Any]]:
    """Stable (JSON-able) description of a `ParallelContext.cache_key()`.
    The Mesh hashes by device identity in-process; across processes the
    equivalent identity is the ordered device (id, platform, kind) list
    plus the axis names/shape and roles."""
    if key is None:
        return None
    mesh, *axis_roles = key
    return {
        "axis_roles": list(axis_roles),
        "axis_names": list(mesh.axis_names),
        "mesh_shape": [int(s) for s in mesh.devices.shape],
        "devices": [
            [int(d.id), str(d.platform),
             str(getattr(d, "device_kind", ""))]
            for d in mesh.devices.flat
        ],
    }


def build_fingerprint_doc(net, kind: str, static: Dict[str, Any],
                          args) -> Dict[str, Any]:
    """The full (pre-hash) fingerprint document for one program at one
    batch signature. Kept JSON-able so the store can write it next to the
    artifact for debuggability."""
    import jax
    import jaxlib

    from deeplearning4j_tpu.kernels import registry as _kernels_registry
    from deeplearning4j_tpu.parallel.context import context_cache_key

    dev = jax.devices()
    return {
        "format": FORMAT_VERSION,
        "engine": type(net).__name__,
        "model": net.conf.to_json(),
        "kind": kind,
        "static": sorted((str(k), repr(v)) for k, v in static.items()),
        "signature": tree_signature(args),
        "context": _context_desc(context_cache_key()),
        # Kernel-registry selection (kernels/registry.py): a knob flip
        # resolves different kernel impls inside the traced program, so a
        # cached executable from another config must not be served.
        "kernels": _kernels_registry.config_fingerprint(),
        # Paged-KV pool geometry (models/zoo.PagedDecodeStepper stamps
        # this on the engine): the page size / pool depth shape the decode
        # program's state arrays, so warmup must ship the real paged
        # executable, never a dense-geometry one. None for dense decode.
        "decode_pool": getattr(net, "_decode_pool_geometry", None),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": str(dev[0].platform) if dev else "none",
        "device_kind": str(getattr(dev[0], "device_kind", "")) if dev else "",
        "num_devices": len(dev),
        "x64": bool(jax.config.jax_enable_x64),
    }


def fingerprint(doc: Dict[str, Any]) -> str:
    """sha256 over the canonical JSON of the fingerprint document."""
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


class AOTStore:
    """Directory of serialized executables: ``<root>/aot/<fp>.jaxec``
    (pickled ``{format, fingerprint, jax, jaxlib, device_ids, payload}``)
    with a ``<fp>.json`` metadata sidecar holding the fingerprint
    document."""

    def __init__(self, root: str):
        self.root = os.path.join(root, "aot")
        self._lock = threading.Lock()
        self._warned: set = set()

    def _path(self, fp: str) -> str:
        return os.path.join(self.root, fp + ".jaxec")

    def _warn_once(self, key: str, message: str) -> None:
        with self._lock:
            if key in self._warned:
                return
            self._warned.add(key)
        warnings.warn(message)

    def load(self, fp: str):
        """Deserialize + load the executable for `fp` onto the devices it
        was compiled for, or None on miss OR any failure (corruption,
        version/device mismatch — the fallback is always a live
        compile)."""
        path = self._path(fp)
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
            if (not isinstance(blob, dict)
                    or blob.get("format") != FORMAT_VERSION
                    or blob.get("fingerprint") != fp):
                raise ValueError("artifact metadata mismatch")
            import jax
            import jaxlib

            if (blob.get("jax") != jax.__version__
                    or blob.get("jaxlib") != jaxlib.__version__):
                # The fingerprint already keys on versions; a mismatch here
                # means the file was renamed or hand-edited. Treat as miss.
                raise ValueError(
                    f"artifact built on jax {blob.get('jax')}/"
                    f"jaxlib {blob.get('jaxlib')}")
            from jax.experimental.serialize_executable import (
                deserialize_and_load)

            by_id = {d.id: d for d in jax.devices()}
            missing = [i for i in blob["device_ids"] if i not in by_id]
            if missing:
                raise ValueError(
                    f"artifact compiled for device ids {blob['device_ids']}"
                    f"; this process has no device {missing}")
            payload, in_tree, out_tree = blob["payload"]
            t0 = time.perf_counter()
            loaded = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in blob["device_ids"]])
            _M_SECONDS_AOT.observe(time.perf_counter() - t0)
            return loaded
        except FileNotFoundError:
            return None
        except Exception as e:
            self._warn_once(fp, (
                f"discarding unusable AOT compile-cache artifact "
                f"{os.path.basename(path)} ({type(e).__name__}: {e}); "
                f"falling back to live compilation — delete the file to "
                f"silence this warning"))
            return None

    def save(self, fp: str, compiled, doc: Dict[str, Any]) -> bool:
        """Serialize `compiled` under `fp` (atomic). Failures are
        non-fatal: the in-process executable keeps working, the artifact
        just isn't shared. Returns True when the artifact was written."""
        try:
            from jax.experimental.serialize_executable import serialize

            payload = serialize(compiled)
            blob = {
                "format": FORMAT_VERSION,
                "fingerprint": fp,
                "jax": doc.get("jax"),
                "jaxlib": doc.get("jaxlib"),
                # In device-assignment order: `load` hands the executable
                # back to exactly these.
                "device_ids": [int(d.id) for d in
                               compiled.runtime_executable().local_devices()],
                "payload": payload,
            }
            os.makedirs(self.root, exist_ok=True)
            data = pickle.dumps(blob, protocol=pickle.HIGHEST_PROTOCOL)
            final = self._path(fp)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(data)
                os.replace(tmp, final)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            meta = json.dumps(doc, sort_keys=True, indent=1)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                f.write(meta)
            os.replace(tmp, final[:-len(".jaxec")] + ".json")
            return True
        except Exception as e:
            self._warn_once("save:" + fp, (
                f"could not write executable {fp[:12]} into the AOT store "
                f"({type(e).__name__}: {e}); this process keeps its "
                f"in-memory program, later processes will recompile"))
            return False
