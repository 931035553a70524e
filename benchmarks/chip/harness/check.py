"""Whether the served tokens are right: the comparison that decides
``correct`` for a serving cell.

After the window, a sample of the requests the engine completed, drawn
from the seed and always holding the longest one, goes through the
configuration's plain reference: one forward pass over each prompt with
its served tokens.  At the position of each served token the reference
gives its own best logit; the number compared is the widest gap by which
a served token's reference logit lies below that best (0 for a token the
reference would also have chosen), or the mean of those gaps over every
served token of the sample; the configuration's ``limits`` name which
of the two its cells compare.  Greedy decoding serves the argmax,
so a correct engine shows only rounding here, and a wrong token,
position, cache entry or state shows as a gap of the logits' own scale.

The control reads the same number for the reference computed in int8 in
place of the program: at each position of the same prompts and tokens,
the gap of the token the int8 reference puts first.  ``judge`` holds the
program's numbers and the control's to the same limits.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence, Tuple

import numpy as np


# The numbers a configuration's ``limits`` may name.
GAP_NUMBERS = ("max_logit_gap", "mean_logit_gap")


def judge(gaps: Mapping[str, Any], limits: Mapping[str, Any],
          failed: int) -> Tuple[Dict[str, Dict[str, float]], bool]:
    """(checks, correct): each gap the configuration limits beside its
    limit, the served tokens checked (at least 1) and the requests that
    failed (none).  ``correct`` holds when every check does."""
    named = [n for n in GAP_NUMBERS if n in limits]
    if not named:
        raise ValueError(f"limits name none of {GAP_NUMBERS}")
    checks = {n: {"value": float(gaps[n]), "limit": float(limits[n])}
              for n in named}
    checks["served_tokens_checked"] = {"value": int(gaps["tokens_checked"]),
                                       "limit": 1}
    checks["failed_requests"] = {"value": int(failed), "limit": 0}
    correct = (gaps["tokens_checked"] >= 1 and failed == 0
               and all(checks[n]["value"] <= checks[n]["limit"]
                       for n in named))
    return checks, bool(correct)


def control_gaps(res: Mapping[str, Any]) -> Dict[str, Any]:
    """The control's readings of ``served_gaps(..., control=True)``
    under the program's names, for ``judge``."""
    return {"max_logit_gap": res["control_max_logit_gap"],
            "mean_logit_gap": res["control_mean_logit_gap"],
            "tokens_checked": res["tokens_checked"]}


def pick_sample(done: Sequence[Tuple[np.ndarray, np.ndarray]], seed: int,
                min_tokens: int, max_requests: int) -> List[int]:
    """Indices of ``done`` (prompt, served tokens) to check: the longest
    request, then others in seeded order until ``min_tokens`` served
    tokens or ``max_requests`` requests are in."""
    if not done:
        return []
    sizes = [len(p) + len(t) for p, t in done]
    longest = int(np.argmax(sizes))
    order = [i for i in np.random.default_rng(seed).permutation(len(done))
             if i != longest]
    picked, served = [longest], len(done[longest][1])
    for i in order:
        if served >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(int(i))
        served += len(done[i][1])
    return picked


def _positions(prompt: np.ndarray, served: np.ndarray, pad_to: int):
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} exceeds the reference "
                         f"length {pad_to}")
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return padded, pos


def served_gaps(ref, sizes: Dict[str, Any], params,
                sample: Sequence[Tuple[np.ndarray, np.ndarray]],
                pad_to: int, control: bool = False) -> Dict[str, Any]:
    """The sample's gaps: the widest (``max_logit_gap``) and the mean
    over every served token (``mean_logit_gap``); with ``control``, the
    same two numbers for the int8 reference's first choice at the same
    positions (``control_max_logit_gap``, ``control_mean_logit_gap``)."""
    import jax.numpy as jnp
    worst = total = 0.0
    c_worst = c_total = 0.0
    tokens = 0
    for prompt, served in sample:
        if len(served) == 0:
            continue
        seq, pos = _positions(prompt, served, pad_to)
        lg = ref.reference_logits(params, sizes, seq, mode="f32")[pos]
        best = jnp.max(lg, axis=-1)
        got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], axis=-1)
        gap = best - got[:, 0]
        worst = max(worst, float(jnp.max(gap)))
        total += float(jnp.sum(gap))
        if control:
            q = ref.reference_logits(params, sizes, seq, mode="int8")[pos]
            pick = jnp.argmax(q, axis=-1)
            alt = jnp.take_along_axis(lg, pick[:, None], axis=-1)
            c_gap = best - alt[:, 0]
            c_worst = max(c_worst, float(jnp.max(c_gap)))
            c_total += float(jnp.sum(c_gap))
        tokens += len(served)
    out = {"max_logit_gap": worst, "mean_logit_gap": total / max(tokens, 1),
           "tokens_checked": tokens, "requests_checked": len(sample)}
    if control:
        out["control_max_logit_gap"] = c_worst
        out["control_mean_logit_gap"] = c_total / max(tokens, 1)
    return out
