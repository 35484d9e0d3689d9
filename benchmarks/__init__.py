"""The benchmark: `python benchmarks/run.py --workload <cell> ...` (README.md)."""
