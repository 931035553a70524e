"""ssm_scan_roofline: the selective-scan kernel's share of its roofline
over the window, for its prefill and decode calls together: the least
time the chip could take for the window's calls over their summed device
time.  A call is one layer of one admission prefill (one row of the real
prompt length) or of one decode step (one token for each live row); its
least time is the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth, counted from live rows and real tokens only.
Kernel time is the summed duration of the kernel's events in the device
trace."""
from harness.kernels import is_ssm_scan_kernel
from harness.serve import admissions, decode_steps


def call_cost(tokens_per_row, *, di, state, carried):
    """(FLOPs, bytes) of one call.  Per token and row: exp(dt A), the
    state update dA h + dt B x and the read-out C h are 7 FLOPs per
    channel and state; x (bf16) and dt (f32) are read per channel, B and
    C (f32) per state, y (bf16) written per channel.  Per row the f32
    state is written, and read first when ``carried`` (decode).  Per call
    A (f32) and D (bf16) are read."""
    t = sum(tokens_per_row)
    rows = len(tokens_per_row)
    flops = 7 * di * state * t
    nbytes = t * (di * 2 + di * 4 + 2 * state * 4 + di * 2)
    nbytes += rows * di * state * 4 * (2 if carried else 1)
    nbytes += di * state * 4 + di * 2
    return flops, nbytes


def read(ctx):
    tr, win, peaks, s = ctx["trace"], ctx["window"], ctx["peaks"], ctx["sizes"]
    evs = tr.op_events(is_ssm_scan_kernel)
    if not evs or "bf16_flops_per_s" not in peaks:
        return None
    kernel_s = sum(e - b for _, _, b, e in evs) / 1e9 / len(tr.ops)
    dims = dict(di=int(s["intermediate_size"]), state=int(s["state_size"]))

    def least(cost):
        return max(cost[0] / peaks["bf16_flops_per_s"],
                   cost[1] / peaks["hbm_bytes_per_s"])
    total = sum(least(call_cost([len(r.prompt)], carried=False, **dims))
                for r in admissions(win, win.t0, win.t_end))
    total += sum(least(call_cost([1] * len(step), carried=True, **dims))
                 for step in decode_steps(win, win.t0, win.t_end))
    total *= int(s["num_hidden_layers"])
    return 100.0 * total / kernel_s if total else None
