"""Chip benchmark of the placement service (see `BENCHMARK.json`, `PERF.md`).

`bench/run.py` runs one cell once.  Everything a cell is made of is data
found by name: `configs/<config>.json`, `traffic/<mix>.json` and one reader
per metric in `metrics/<metric>.py`.  `reference.py` is the plain numpy
yardstick that decides `correct`; it imports nothing from `src/repro`.
"""
