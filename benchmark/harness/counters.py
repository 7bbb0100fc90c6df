"""Reads of the program's own counters (sums, counts, deltas of host time)."""

from __future__ import annotations

import logging


def _family(name: str):
    from deeplearning4j_tpu import observability as obs

    return obs.metrics.get_family(name)


def counter_total(name: str, **labels) -> float:
    fam = _family(name)
    if fam is None:
        return 0.0
    return sum(c.get() for c in fam.children()
               if all(c.labels.get(k) == v for k, v in labels.items()))


def histogram_sum_count(name: str, **labels):
    """(sum, count) of a histogram family over the children that match."""
    fam = _family(name)
    if fam is None:
        return 0.0, 0
    total, count = 0.0, 0
    for c in fam.children():
        if all(c.labels.get(k) == v for k, v in labels.items()):
            _, _, s, n = c.histogram_state()
            total, count = total + s, count + n
    return total, count


class Snapshot:
    """Values of named readings at one moment; `delta` against an earlier one."""

    def __init__(self, readings: dict):
        self.values = {k: fn() for k, fn in readings.items()}

    def delta(self, earlier: "Snapshot") -> dict:
        out = {}
        for k, v in self.values.items():
            e = earlier.values[k]
            out[k] = (tuple(a - b for a, b in zip(v, e))
                      if isinstance(v, tuple) else v - e)
        return out


class CompileNames(logging.Handler):
    """Names of what jax compiles while installed (`jax_log_compiles`): a
    compile inside the window is reported by name. Copied from
    chip_smoke.py."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg[len("Compiling "):].split(" with ")[0])

    def __enter__(self):
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax

        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", False)
