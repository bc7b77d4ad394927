"""Per-layer metric readers, one module per metric named in
``BENCHMARK.json``; each has ``read(run) -> float | None``."""
