"""Per-layer metric readers, one module per metric named in
``BENCHMARK.json``. Each has ``read(view) -> float | None`` over a
``bench.trace.TraceView``; ``None`` means the trace holds nothing to
read, and the harness then leaves the metric out of the line."""
