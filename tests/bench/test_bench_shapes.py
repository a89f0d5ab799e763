"""The kernel shape functions against hand counts, and the roofline
share they feed."""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.metrics import (maint_kernel_roofline, round_kernel_roofline,  # noqa: E402
                           roofline)


def cell(K, M):
    return types.SimpleNamespace(
        K=K, M=M, config={"max_clients": 8, "ring": 64, "maint_every": 10},
        mix={"base_clients": 4})


# (K, M): (round flops, round bytes, maint flops, maint bytes)
HAND = {
    # round: state 2*30*10*(4+4+4+4+1+4) = 12600, rtt 30*10*4 = 1200,
    # outputs 30*8*(4+4+4) = 2880, 120 issued * 16 = 1920, rptr 2*30*4
    # = 240; ops 5*30*10*8. maint: 3 due proxies * 10 arms * (64*8 +
    # 4 + 8); ops 30 * 3 * 10 * 64.
    (30, 10): (12_000, 18_840, 57_600, 15_720),
    # round: 2_100_000 + 200_000 + 96_000 + 64_000 + 8_000; maint: 100
    # due proxies * 50 * 524 = 2.62 MB, about 3.2 us at 819 GB/s
    (1000, 50): (2_000_000, 2_468_000, 9_600_000, 2_620_000),
}


@pytest.mark.parametrize("K,M", sorted(HAND))
def test_shape_hand_counts(K, M):
    rf, rb, mf, mb = HAND[(K, M)]
    assert round_kernel_roofline.shape(cell(K, M)) == (rf, rb)
    assert maint_kernel_roofline.shape(cell(K, M)) == (mf, mb)


def test_roofline_share_is_least_time_over_kernel_time():
    c = cell(1000, 50)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 100 steps, each round kernel call 10 us on the device
    ops = [("fusion.1", 0, 5_000)] + [
        (f"%{round_kernel_roofline.KERNEL}.1", 10_000 * i,
         10_000 * i + 10_000) for i in range(1, 101)]
    view = types.SimpleNamespace(
        cell=c, lane_steps=100, peaks=peaks,
        op_seconds=lambda match: (
            sum(e - s for n, s, e in ops if match(n)) / 1e9,
            sum(1 for n, _, _ in ops if match(n))))
    share = round_kernel_roofline.read(view)
    least = 2_468_000 / 819e9            # bytes bound it
    assert share == pytest.approx(100 * least / 10e-6)
    assert maint_kernel_roofline.read(view) is None     # nothing to read
    assert roofline.share(types.SimpleNamespace(
        peaks=None, lane_steps=1), None, None) is None
