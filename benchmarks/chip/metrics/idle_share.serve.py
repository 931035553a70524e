"""idle_share.serve: the share of the window in which no operation ran
on the chip: 1 - (union of the device's operation intervals) / window,
from the device trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
