"""Scenario event kinds the benchmark's traffic generator knows, one
module per kind, found by the ``kind`` a mix file names. Each module
gives ``program_event(ev, horizon, K, M)``, the program's own event
object (its input format), and ``apply(arrs, ev, horizon, dt, K, M)``,
the same event written into the benchmark's own driver arrays."""
