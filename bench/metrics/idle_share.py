"""idle_share: 1 minus the union of device-busy intervals over the traced
slice, in percent, averaged over the chips the cell uses."""


def read(run):
    if run.view.window_s <= 0 or not run.view.devices:
        return None
    return 100.0 * (1.0 - run.view.busy_s() / run.view.window_s)
