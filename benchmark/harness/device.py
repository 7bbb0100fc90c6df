"""The device the run is on, the published peaks, and where compiled code is kept."""

from __future__ import annotations

import os
import sys

# Published peaks of one chip, keyed by the exact `device_kind` JAX reports:
# (bf16 FLOP/s, HBM bytes/s, source). A device that is not here is an error.
# Copied from deeplearning4j_tpu/observability/profiler.py::CHIP_PEAKS so
# that no later PR can move the yardstick.
CHIP_PEAKS = {
    "TPU v5 lite": (197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
    "TPU v5e": (197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}

CACHE_DIRNAME = ".bench_compile_cache"


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache and the program's AOT store go to one fixed
    directory inside the checkout, whatever the machine's environment
    names. Called before jax is imported: the program takes the directory
    from this variable and sets none of its own."""
    path = os.path.join(root, CACHE_DIRNAME)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    # A size limit turns on JAX's LRU eviction, which on the chip machine
    # (192 MB) failed every write beside the AOT store's files and so made
    # every run compile everything again (PERF.md, PR 23).
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    return path


def require(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. Off the TPU, or with fewer chips than
    the cell asks for, the process exits non-zero and prints no result;
    only a rehearsal (which can never print `correct: true`) goes on."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu" and not rehearsal:
        sys.exit(f"the benchmark needs a TPU and jax found {dev}: there is "
                 "no CPU fallback (--rehearsal runs the control flow at a "
                 "tiny size and reports correct: false)")
    if chips > dev["count"]:
        sys.exit(f"the cell needs {chips} chip(s); jax found {dev}")
    if dev["platform"] == "tpu" and dev["kind"] not in CHIP_PEAKS:
        sys.exit(f"no published peak for device_kind {dev['kind']!r}")
    return dev


def peak_flops(kind: str) -> float:
    return CHIP_PEAKS[kind][0]


def memory_peak_bytes(chips: int, program_bytes: int = 0) -> int:
    """Peak device memory on the fullest of the chips used.

    `memory_stats()["peak_bytes_in_use"]` on this backend counts live arrays
    and not a running program's temporaries (PERF.md, PR 21: 0.50 GB beside
    9.68 GiB of temporaries); `peak_bytes_reserved`, what the allocator took
    from the chip, does see them (10.35 GB for the same step, PR 23), so the
    allocator's reading is the larger of the two. `program_bytes` is what the largest program
    of the window needs while it runs (arguments + outputs + temporaries
    - aliased, from the compiled executable's own memory analysis); the peak
    is whichever is larger: the allocator's reading, or that program's
    footprint."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use") or 0),
                         int(stats.get("peak_bytes_reserved") or 0)))
    return max(max(peaks), int(program_bytes))


def program_footprint_bytes(executables) -> int:
    """The largest `arguments + outputs + temporaries - aliased` over the
    compiled executables given (per device)."""
    best = 0
    for exe in executables:
        try:
            m = exe.memory_analysis()
        except Exception:  # a plain jit callable has none
            continue
        if m is None:
            continue
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        best = max(best, int(total))
    return best
