"""On-chip benchmark of the aggregator: see run.py and PERF.md."""
