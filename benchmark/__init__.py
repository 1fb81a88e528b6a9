"""The benchmark of tpu-paddle. See PERF.md; entry point: run.py."""
