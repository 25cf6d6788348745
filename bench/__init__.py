"""On-chip benchmark of the SpGEMM engine (see ``run.py`` and PERF.md)."""
