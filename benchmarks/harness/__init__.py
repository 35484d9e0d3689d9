"""The harness proper. Nothing about one cell, one configuration, one traffic
mix or one per-layer metric lives here: those are data files found by the
names in BENCHMARK.json (see ../README.md)."""
