"""prefill_share: device time of the engine's admission prefill
executables in the window, as a share of the window, from the device
trace (``XLA Modules`` events of the prefill program)."""
from harness.kernels import is_prefill_module


def read(ctx):
    tr = ctx["trace"]
    evs = tr.module_events(is_prefill_module)
    if not tr.modules:
        return None
    n = max(len(tr.modules), 1)
    busy = sum(e - s for _, _, s, e in evs) / n / 1e9
    return 100.0 * busy / tr.window_s()
