"""model_mfu.serve: the whole model step's share of the chip's bf16 peak:
2 x (parameters in matrix products) x (tokens processed in the window)
/ window / peak.  Tokens processed are the real prompt tokens of every
admission (prefill) and every token a decode step produced, in the
window; padding is not counted."""
from harness.serve import admissions, decode_steps


def read(ctx):
    win, peaks = ctx["window"], ctx["peaks"]
    if "bf16_flops_per_s" not in peaks:
        return None
    tokens = sum(len(r.prompt) for r in admissions(win, win.t0, win.t_end))
    tokens += sum(len(s) for s in decode_steps(win, win.t0, win.t_end))
    if not tokens:
        return None
    flops = 2.0 * ctx["matmul_params"] * tokens
    return 100.0 * flops / (win.t_end - win.t0) / peaks["bf16_flops_per_s"]
