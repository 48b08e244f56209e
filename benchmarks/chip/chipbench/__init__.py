"""On-chip benchmark harness for the Dooly reproduction.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` under ``benchmarks/chip``.  Adding a configuration,
a traffic mix or a per-layer metric adds files; no module here changes.
"""
