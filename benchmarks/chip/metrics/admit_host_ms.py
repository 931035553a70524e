"""admit_host_ms: device idle time inside admissions, per admission: the
idle gaps of the device (at least 20 us) that lie inside the union of
the engine's ``serve.admit.{prepare,first_token,place}`` and
``serve.prefill`` spans in the window, over the ``serve.admit.prepare``
spans (one per admission) that start in it.  A program that records no
host phases yields nothing."""
from harness.spans import ADMIT_PHASES, idle_ms_per


def read(ctx):
    return idle_ms_per(ctx["trace"], ADMIT_PHASES, "serve.admit.prepare")
