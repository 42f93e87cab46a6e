"""The comparison that decides ``correct``: served greedy tokens against
the plain reference.

A sample of the finished requests, drawn from the seed and always
holding the one with the most served tokens, is run through the
reference once over its prompt and served tokens.  At each served
position the reference's best logit minus its logit of the token that
was served is a gap; the run's number is the widest gap.  A served token
that the reference also ranks first has gap 0; rounding in the served
precision can only flip near-ties, so sound runs read small gaps and a
wrong token reads a large one.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def draw_sample(records: Sequence, seed: int, min_tokens: int,
                max_requests: int, min_requests: int) -> List:
    """The finished request with the most tokens, then others in an order
    drawn from the seed, until at least ``min_requests`` requests and
    ``min_tokens`` served tokens, or ``max_requests`` requests."""
    done = [r for r in records if r.status == "completed" and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) & (2**64 - 1), 7]) \
        .permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if (n >= min_tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _positions(prompt: np.ndarray, served: Sequence[int]):
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return seq.astype(np.int32), rows


def widest_gap(ref, model: Dict, weights, sample: Sequence,
               prompts: Dict[int, np.ndarray]) -> float:
    """Largest reference-logit gap of a served token over the sample."""
    worst = 0.0
    for rec in sample:
        seq, rows = _positions(prompts[rec.index], rec.tokens)
        lg = ref.logits_at(model, weights, seq, rows)
        served = np.asarray(rec.tokens)
        gap = lg.max(-1) - lg[np.arange(len(served)), served]
        worst = max(worst, float(gap.max()))
    return worst


def control_gap(ref, model: Dict, weights, sample: Sequence,
                prompts: Dict[int, np.ndarray],
                precision: str = "fp8") -> float:
    """The same number for the reference computed at ``precision``: at
    each position of the same prompts and served tokens, the gap of the
    token that the lower precision ranks first."""
    worst = 0.0
    for rec in sample:
        seq, rows = _positions(prompts[rec.index], rec.tokens)
        lg = ref.logits_at(model, weights, seq, rows)
        low = ref.logits_at(model, weights, seq, rows, precision=precision)
        pick = low.argmax(-1)
        gap = lg.max(-1) - lg[np.arange(len(pick)), pick]
        worst = max(worst, float(gap.max()))
    return worst
