"""Engine step: device time per simulated step in the metric
accumulator (``sim.accumulator``: ``update_accumulator`` and the
per-step series sums) [us]."""
from bench.metrics import phases


def read(view):
    return phases.device_us(view, "sim.accumulator")
