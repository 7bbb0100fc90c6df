"""The chip benchmark: everything `BENCHMARK.json` runs lives under here.

Later PRs add files (a configuration, a cell, a per-layer metric) and one
entry to `BENCHMARK.json`; they edit nothing that is here.
"""
