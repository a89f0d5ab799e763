"""Engine step: device time per simulated step in maintenance
(``sim.maintenance``: the due proxies' estimate, the Pallas
maintenance kernel and the gather and scatter around it) [us]."""
from bench.metrics import phases


def read(view):
    return phases.device_us(view, "sim.maintenance")
