"""device_idle_share: percent of the traced window in which no operation
ran on the device (1 - union of the device's operation intervals over
the window, averaged over the chips used).  Moves ``events_per_s``."""


def read(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
