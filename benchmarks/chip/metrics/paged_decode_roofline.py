"""paged_decode_roofline: the paged decode attention kernel's share of
its roofline over the window: the least time the chip could take for the
window's calls over their summed device time.  A call is one layer of
one decode step over all rows; its least time is the larger of its FLOPs
over the bf16 peak and its bytes over the HBM bandwidth, counted from
each row's live context (the positions it attends to), never from the
padded block table, so that the same work is read whatever implements
it.  Kernel time is the summed duration of the kernel's events in the
device trace."""
from harness.kernels import is_paged_decode_kernel
from harness.serve import decode_steps


def call_cost(contexts, *, heads, kv_heads, head_dim, elem_bytes=2):
    """(FLOPs, bytes) of one call over rows with these live contexts:
    q.K and p.V are 2 x head_dim FLOPs per head per position each; K and
    V of every live position are read, q read and the output written."""
    n = sum(contexts)
    flops = 4 * heads * head_dim * n
    nbytes = (2 * kv_heads * head_dim * n * elem_bytes
              + len(contexts) * 2 * heads * head_dim * elem_bytes)
    return flops, nbytes


def read(ctx):
    tr, win, peaks, s = ctx["trace"], ctx["window"], ctx["peaks"], ctx["sizes"]
    evs = tr.op_events(is_paged_decode_kernel)
    if not evs or "bf16_flops_per_s" not in peaks:
        return None
    kernel_s = sum(e - b for _, _, b, e in evs) / 1e9 / len(tr.ops)
    heads = int(s["num_attention_heads"])
    dims = dict(heads=heads, kv_heads=int(s["num_key_value_heads"]),
                head_dim=int(s["hidden_size"]) // heads)
    least = 0.0
    for step in decode_steps(win, win.t0, win.t_end):
        flops, nbytes = call_cost([c for _, c in step], **dims)
        least += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    least *= int(s["num_hidden_layers"])
    return 100.0 * least / kernel_s if least else None
