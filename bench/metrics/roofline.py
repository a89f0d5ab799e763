"""Shared arithmetic of the kernel roofline readers: the least time the
chip could take for the window's kernel calls, from the shape
function's operations and bytes and the chip's peaks, over the summed
device time of those calls [%]. The bound is whichever of operations
over peak FLOP/s and bytes over peak HBM bandwidth is larger."""


def share(view, match, per_lane_step):
    if view.peaks is None or not view.lane_steps:
        return None
    seconds, calls = view.op_seconds(match)
    if calls == 0 or seconds <= 0:
        return None
    flops, nbytes = per_lane_step(view.cell)
    least = view.lane_steps * max(flops / view.peaks["flops_per_s"],
                                  nbytes / view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
