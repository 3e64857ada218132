"""The benchmark of the served path: `python -m benchmarks.run`. See PERF.md."""
