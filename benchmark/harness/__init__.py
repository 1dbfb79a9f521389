"""The benchmark's general machinery. Nothing in this package names a cell,
a configuration or a traffic mix: those are data files beside it, found by
the names in BENCHMARK.json (see ../README.md)."""
