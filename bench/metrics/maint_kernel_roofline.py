"""Kernels: the fused maintenance kernel (``kernels/kde.py``), one call
per simulated step over the proxies whose decision clock fires, against
its roofline [%].

The shape function counts what the estimate needs: the due proxies'
latency and timestamp rings read (K / maint_every proxies x M x R x
8 bytes), their RTT rows read and two estimates per (proxy, instance)
written. Operations: per ring entry the window test, the masked mean
and variance terms, the standardisation and the Gaussian CDF (an erf
of 11 multiply-adds and a divide), about 30.
"""
from bench.metrics import roofline

KERNEL = "fused_maintenance"


def match(name: str) -> bool:
    """The kernel's own ops: their HLO names start with the jitted
    function that wraps the pallas_call."""
    return name.lstrip("%").startswith(KERNEL)


def shape(cell):
    K, M, R = cell.K, cell.M, cell.config["ring"]
    due = -(-K // cell.config["maint_every"])
    nbytes = due * M * (R * 8 + 4 + 8)
    flops = 30 * due * M * R
    return flops, nbytes


def read(view):
    return roofline.share(view, match, shape)
