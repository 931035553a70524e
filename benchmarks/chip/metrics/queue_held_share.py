"""queue_held_share: the share of the window in which the queue's head
waited with an engine row free: the union of the engine's
``serve.queue.held`` spans (a wider table, a full KV pool, injected
exhaustion or an admission hold), clipped to the window, over the
window.  0 when the program records host phases but the head never
waited so; nothing from a program that records no phases."""
from harness.spans import has_phases, intervals
from harness.trace import total


def read(ctx):
    tr = ctx["trace"]
    if not has_phases(tr):
        return None
    lo, hi = tr.window
    return 100.0 * total(intervals(tr, ["serve.queue.held"])) / (hi - lo)
