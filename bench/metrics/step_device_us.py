"""Engine step: device-busy time of the traced window per simulated
fleet step drained in it [us]; a grid counts one step per lane."""


def read(view):
    if not view.lane_steps:
        return None
    return 1e6 * view.busy_s / view.lane_steps
