"""queue_wait_p90_s: 90th percentile, over every request due in the
window, of the time from its due time to its admission into an engine
row (the lifecycle log's admission timestamp); a request not admitted by
the end of the window counts at its age then."""
from harness.stats import censored, percentile


def read(ctx):
    win = ctx["window"]
    return percentile(censored([r.due for r in win.requests],
                               [r.admitted for r in win.requests],
                               win.t_end), 90)
