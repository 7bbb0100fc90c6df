"""Thread-safe metrics registry: labeled counters / gauges / histograms.

The production observability core the reference never had (its telemetry is
the listener -> StatsStorage -> Play UI pipeline, which answers "how is
training going", not "where did this step's milliseconds go on a live
serving box"). Design constraints, in order:

1. Near-zero cost when disabled: every mutator checks one bool before doing
   anything else, so `DL4J_TPU_OBS=0` leaves sub-microsecond no-ops in the
   hot loops (enforced by the overhead test in `tests/test_observability.py`).
2. Hot-loop friendly when enabled: callers resolve `.labels(...)` children
   ONCE at module import; `inc()`/`observe()` on a child is a bool check,
   one lock, one float op.
3. Standard exposition: the Prometheus text format 0.0.4 (label escaping,
   histogram `_bucket`/`_sum`/`_count` triplets, cumulative `le` buckets)
   so any scraper works, plus a JSON snapshot for embedding in
   BENCH_out.json.

Collectors (process RSS, JAX live device buffers) run at scrape time only —
they never touch the training path.
"""

from __future__ import annotations

import bisect
import re
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.analysis.locktrace import named_rlock

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

# Latency-shaped default buckets (seconds): spans µs-level dispatches to
# multi-second cold XLA compiles.
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

# Wide ladder for families whose observations routinely run multi-second
# to multi-minute (XLA compiles, serving requests riding a cold model
# reload, TTFT behind a long prefill). The default ladder tops out at 30s,
# which would clamp such a family's p99 into `+Inf` — the acceptance smoke
# asserts no scraped family has a majority of observations there.
WIDE_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                5.0, 10.0, 30.0, 60.0, 120.0, 300.0, 600.0)


def _escape_label(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _fmt(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _label_str(labels: Dict[str, str], extra: Optional[Tuple[str, str]] = None) -> str:
    items = list(labels.items())
    if extra is not None:
        items.append(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape_label(v)}"' for k, v in items) + "}"


class _Child:
    """One labeled series. All mutators fast-path the registry's enabled
    flag before taking the lock."""

    __slots__ = ("_reg", "labels", "_value", "_sum", "_count", "_bucket_counts",
                 "_buckets", "_fn")

    def __init__(self, reg: "MetricsRegistry", labels: Dict[str, str],
                 buckets: Optional[Sequence[float]] = None):
        self._reg = reg
        self.labels = labels
        self._value = 0.0
        self._fn: Optional[Callable[[], float]] = None
        self._buckets = None if buckets is None else tuple(buckets)
        if self._buckets is not None:
            self._bucket_counts = [0] * (len(self._buckets) + 1)  # + +Inf
            self._sum = 0.0
            self._count = 0

    # counter / gauge
    def inc(self, v: float = 1.0) -> None:
        if not self._reg._enabled:
            return
        with self._reg._lock:
            self._value += v

    def set(self, v: float) -> None:
        if not self._reg._enabled:
            return
        with self._reg._lock:
            self._value = float(v)

    def set_function(self, fn: Optional[Callable[[], float]]) -> None:
        """Scrape-time gauge: `fn()` is called at exposition (queue depths,
        live-buffer counts — things that have a current value, not a path
        through the hot loop)."""
        self._fn = fn

    def get(self) -> float:
        if self._fn is not None:
            try:
                return float(self._fn())
            except Exception:
                return float("nan")
        return self._value

    # histogram
    def observe(self, v: float) -> None:
        if not self._reg._enabled:
            return
        with self._reg._lock:
            self._bucket_counts[bisect.bisect_left(self._buckets, v)] += 1
            self._sum += v
            self._count += 1

    def histogram_state(self):
        """(buckets, cumulative_counts_incl_inf, sum, count) snapshot."""
        with self._reg._lock:
            raw = list(self._bucket_counts)
            s, c = self._sum, self._count
        cum, running = [], 0
        for n in raw:
            running += n
            cum.append(running)
        return self._buckets, cum, s, c

    def summarize(self, quantiles=(0.5, 0.9, 0.99)) -> Dict[str, float]:
        """Bucket-interpolated quantile summary (for BENCH_out.json)."""
        buckets, cum, s, c = self.histogram_state()
        out: Dict[str, float] = {"count": c, "sum": s}
        if not c:
            return out
        out["mean"] = s / c
        edges = list(buckets) + [float("inf")]
        for q in quantiles:
            target = q * c
            prev_cum, lo = 0, 0.0
            val = edges[-2] if len(edges) > 1 else 0.0
            for i, cm in enumerate(cum):
                if cm >= target:
                    hi = edges[i]
                    if hi == float("inf"):
                        hi = edges[i - 1] if i else 0.0
                    inbucket = cm - prev_cum
                    frac = ((target - prev_cum) / inbucket) if inbucket else 1.0
                    val = lo + (hi - lo) * frac
                    break
                prev_cum, lo = cm, edges[i]
            out[f"p{int(q * 100)}"] = val
        return out


class _Family:
    __slots__ = ("_reg", "name", "help", "kind", "label_names", "_children",
                 "_buckets", "_default")

    def __init__(self, reg, name, help_, kind, label_names, buckets=None):
        self._reg = reg
        self.name = name
        self.help = help_
        self.kind = kind
        self.label_names = tuple(label_names)
        self._buckets = buckets
        self._children: Dict[Tuple[str, ...], _Child] = {}
        self._default = None if self.label_names else self.labels()

    def labels(self, **kv: str) -> _Child:
        if tuple(sorted(kv)) != tuple(sorted(self.label_names)):
            raise ValueError(
                f"{self.name}: expected labels {self.label_names}, got "
                f"{tuple(kv)}")
        key = tuple(str(kv[n]) for n in self.label_names)
        with self._reg._lock:
            child = self._children.get(key)
            if child is None:
                child = _Child(self._reg, dict(zip(self.label_names, key)),
                               buckets=self._buckets)
                self._children[key] = child
        return child

    # unlabeled convenience: family acts as its own single child
    def _only(self) -> _Child:
        if self._default is None:
            raise ValueError(f"{self.name} is labeled; call .labels(...)")
        return self._default

    def inc(self, v: float = 1.0) -> None:
        self._only().inc(v)

    def set(self, v: float) -> None:
        self._only().set(v)

    def set_function(self, fn) -> None:
        self._only().set_function(fn)

    def get(self) -> float:
        return self._only().get()

    def observe(self, v: float) -> None:
        self._only().observe(v)

    def summarize(self, **kw):
        return self._only().summarize(**kw)

    def children(self) -> List[_Child]:
        with self._reg._lock:
            return list(self._children.values())


class MetricsRegistry:
    """See module docstring. One instance (`deeplearning4j_tpu.observability
    .metrics`) is the process-global default; tests build their own."""

    def __init__(self, enabled: bool = True):
        self._enabled = bool(enabled)
        self._lock = named_rlock("observability.metrics")
        self._families: Dict[str, _Family] = {}
        self._collectors: List[Callable[["MetricsRegistry"], None]] = []

    # ------------------------------------------------------------ lifecycle

    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def reset(self) -> None:
        """Drop all recorded values (families and collectors survive)."""
        with self._lock:
            for fam in self._families.values():
                for child in fam._children.values():
                    child._value = 0.0
                    if child._buckets is not None:
                        child._bucket_counts = [0] * (len(child._buckets) + 1)
                        child._sum = 0.0
                        child._count = 0

    # ------------------------------------------------------------- creation

    def _family(self, name, help_, kind, label_names, buckets=None) -> _Family:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in label_names:
            if not _LABEL_RE.match(ln):
                raise ValueError(f"invalid label name {ln!r}")
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.label_names != tuple(label_names):
                    raise ValueError(
                        f"metric {name} already registered as {fam.kind}"
                        f"{fam.label_names}, cannot re-register as {kind}"
                        f"{tuple(label_names)}")
                return fam
            fam = _Family(self, name, help_, kind, label_names, buckets)
            self._families[name] = fam
            return fam

    def counter(self, name: str, help: str = "",
                label_names: Sequence[str] = ()) -> _Family:
        return self._family(name, help, "counter", label_names)

    def gauge(self, name: str, help: str = "",
              label_names: Sequence[str] = ()) -> _Family:
        return self._family(name, help, "gauge", label_names)

    def histogram(self, name: str, help: str = "",
                  label_names: Sequence[str] = (),
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> _Family:
        return self._family(name, help, "histogram", label_names,
                            buckets=tuple(sorted(buckets)))

    def register_collector(self, fn: Callable[["MetricsRegistry"], None]) -> None:
        """`fn(registry)` runs at every scrape; failures are swallowed (a
        broken collector must not take down /metrics)."""
        self._collectors.append(fn)

    def get_family(self, name: str) -> Optional[_Family]:
        return self._families.get(name)

    # ----------------------------------------------------------- exposition

    def _run_collectors(self) -> None:
        for fn in list(self._collectors):
            try:
                fn(self)
            except Exception:
                pass

    def to_prometheus(self, names: Optional[Sequence[str]] = None) -> str:
        """Prometheus text format 0.0.4. `names` narrows the exposition
        to the listed families — a needle scrape (the fleet router's load
        poll) then costs O(requested families), not O(all families), and
        skips the scrape-time collectors entirely."""
        if names is None:
            self._run_collectors()
        lines: List[str] = []
        with self._lock:
            fams = sorted(self._families.values(), key=lambda f: f.name)
        if names is not None:
            wanted = frozenset(names)
            fams = [f for f in fams if f.name in wanted]
        for fam in fams:
            children = fam.children()
            if not children:
                continue
            if fam.help:
                lines.append(f"# HELP {fam.name} {_escape_label(fam.help)}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            for child in children:
                if fam.kind == "histogram":
                    buckets, cum, s, c = child.histogram_state()
                    for le, cm in zip(buckets, cum[:-1]):
                        lines.append(
                            f"{fam.name}_bucket"
                            f"{_label_str(child.labels, ('le', _fmt(le)))}"
                            f" {cm}")
                    lines.append(
                        f"{fam.name}_bucket"
                        f"{_label_str(child.labels, ('le', '+Inf'))} {c}")
                    lines.append(
                        f"{fam.name}_sum{_label_str(child.labels)} {repr(float(s))}")
                    lines.append(
                        f"{fam.name}_count{_label_str(child.labels)} {c}")
                else:
                    lines.append(
                        f"{fam.name}{_label_str(child.labels)} "
                        f"{_fmt(child.get())}")
        return "\n".join(lines) + "\n"

    def to_json(self, names: Optional[Sequence[str]] = None) -> Dict[str, Any]:
        """Structured snapshot (BENCH_out.json embedding, /metrics?format=json).
        `names` narrows to the listed families and skips collectors (see
        `to_prometheus`)."""
        if names is None:
            self._run_collectors()
        out: Dict[str, Any] = {}
        with self._lock:
            fams = list(self._families.values())
        if names is not None:
            wanted = frozenset(names)
            fams = [f for f in fams if f.name in wanted]
        for fam in fams:
            series = []
            for child in fam.children():
                if fam.kind == "histogram":
                    buckets, cum, s, c = child.histogram_state()
                    series.append({
                        "labels": child.labels,
                        "count": c, "sum": s,
                        "buckets": {_fmt(le): cm
                                    for le, cm in zip(buckets, cum[:-1])},
                        "summary": child.summarize(),
                    })
                else:
                    series.append({"labels": child.labels,
                                   "value": child.get()})
            if series:
                out[fam.name] = {"type": fam.kind, "help": fam.help,
                                 "series": series}
        return out


# -------------------------------------------------------- built-in collectors


def _host_rss_bytes() -> Optional[float]:
    try:
        import os

        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return float(pages * os.sysconf("SC_PAGE_SIZE"))
    except Exception:
        return None


def backend_is_up() -> bool:
    """Whether this process has already initialised a jax backend. Asking
    jax for its devices, its default backend or its live arrays would
    initialise one, and on a TPU host take the chip: a scrape must only
    ever report a backend the process brought up for its own work."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def install_builtin_collectors(reg: MetricsRegistry) -> None:
    """Process RSS + JAX live device buffers, sampled at scrape time."""
    rss = reg.gauge("dl4j_process_resident_memory_bytes",
                    "Resident set size of this process")
    live = reg.gauge("dl4j_jax_live_buffers",
                     "Live jax.Array buffers held by this process")
    live_bytes = reg.gauge("dl4j_jax_live_buffer_bytes",
                           "Total bytes of live jax.Array buffers")

    def collect(_reg: MetricsRegistry) -> None:
        v = _host_rss_bytes()
        if v is not None:
            rss.set(v)
        if not backend_is_up():  # no backend, no buffers: report nothing
            return
        import jax

        arrays = jax.live_arrays()
        live.set(len(arrays))
        live_bytes.set(sum(getattr(a, "nbytes", 0) for a in arrays))

    reg.register_collector(collect)
