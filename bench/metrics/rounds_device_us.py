"""Engine step: device time per simulated step in the C request rounds
(``sim.rounds``: the round scan with selection, the breaker veto, the
unrolled attempts, the queue recursion and the players psum, or the
fused round kernel) [us]."""
from bench.metrics import phases


def read(view):
    return phases.device_us(view, "sim.rounds")
