"""A benchmark of the repository on the chip: see run.py and PERF.md."""
