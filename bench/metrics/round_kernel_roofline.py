"""Kernels: the round megakernel (``kernels/round_fused.py``), one call
per simulated step, against its roofline [%].

The shape function counts what one step's C request rounds need, not
what the kernel moves: the (K, M) routing state read and written once
(weights, SWRR credits, error counters and cooldowns f32 or i32, the
pool bit one byte, ring pointers i32), the (K, M) RTT read, the (K, C)
per-request outputs (choice, latency, processing time) written, and per
issued request one latency/timestamp entry and one reward/timestamp
entry written. Operations: per proxy and round, the SWRR credit add,
the winner's subtraction, the weight total and the renormalisation's
sum and divide, five per arm.
"""
from bench.metrics import roofline

KERNEL = "round_step_swrr"


def match(name: str) -> bool:
    """The kernel's own ops: their HLO names start with the jitted
    function that wraps the pallas_call."""
    return name.lstrip("%").startswith(KERNEL)


def shape(cell):
    K, M, C = cell.K, cell.M, cell.config["max_clients"]
    issued = K * min(cell.mix["base_clients"], C)
    state = 2 * K * M * (4 + 4 + 4 + 4 + 1 + 4)
    nbytes = (state + K * M * 4 + K * C * 3 * 4 + issued * 16
              + 2 * K * 4)
    flops = 5 * K * M * C
    return flops, nbytes


def read(view):
    return roofline.share(view, match, shape)
