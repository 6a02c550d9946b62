"""Per-layer metric readers, one file per metric named as in BENCHMARK.json,
plus the shared counting and trace-reduction helpers (``_counting``,
``_trace``)."""
