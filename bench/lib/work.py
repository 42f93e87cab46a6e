"""Operations and bytes that the algorithm needs, from shapes and
lengths alone, whatever implements it.  ``m`` is
``models.<reference>.dims(model)``; bytes assume the bfloat16 pool and
activations that the configurations serve."""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

BYTES = 2  # bfloat16


def matmul_params_per_layer(m: Dict) -> int:
    d, H, Hk, D, F = m["d"], m["heads"], m["kv_heads"], m["head_dim"], \
        m["ff"]
    return d * H * D + 2 * d * Hk * D + H * D * d + 3 * d * F


def kv_bytes_per_token(m: Dict) -> int:
    """K and V of one token, all layers."""
    return 2 * m["kv_heads"] * m["head_dim"] * BYTES * m["layers"]


def decode_token_flops(m: Dict, ctx: int) -> float:
    """One decode pass of one sequence whose new token sees ``ctx``
    positions (itself included): every matrix product, the head, and
    attention's two products."""
    attn = 4 * m["heads"] * m["head_dim"] * ctx * m["layers"]
    return 2.0 * (m["layers"] * matmul_params_per_layer(m)
                  + m["d"] * m["vocab"]) + attn


def prefill_flops(m: Dict, prompt: int) -> float:
    """Prefill of a ``prompt``-token sequence: the layers for every token,
    causal attention, and the head once for the last token."""
    attn = 4 * m["heads"] * m["head_dim"] * prompt * (prompt + 1) / 2 \
        * m["layers"]
    return 2.0 * (m["layers"] * matmul_params_per_layer(m) * prompt
                  + m["d"] * m["vocab"]) + attn


def chunks(prompt: int, cap: int) -> Iterable[Tuple[int, int]]:
    """(start, length) of each prefill chunk of a ``prompt``-token
    sequence under a chunk cap (the deployment's ``prefill_chunk``)."""
    for start in range(0, prompt, cap):
        yield start, min(cap, prompt - start)


def decode_attn_work(m: Dict, ctx: int) -> Tuple[float, float]:
    """(flops, bytes) of the decode attention kernel for one sequence at
    context ``ctx``, all layers: read K/V of every position, read q,
    write the output."""
    H, D, L = m["heads"], m["head_dim"], m["layers"]
    flops = 4.0 * H * D * ctx * L
    bts = kv_bytes_per_token(m) * ctx + 2 * H * D * BYTES * L
    return flops, bts


def prefill_attn_work(m: Dict, start: int, n: int) -> Tuple[float, float]:
    """(flops, bytes) of the prefill attention kernel for one sequence's
    chunk of ``n`` new tokens after ``start`` cached ones, all layers:
    causal scores and values, reading the cached K/V once and the new
    q/k/v, writing the new K/V into the pool and the output."""
    H, Hk, D, L = m["heads"], m["kv_heads"], m["head_dim"], m["layers"]
    flops = 4.0 * H * D * (n * start + n * (n + 1) / 2) * L
    per_tok_kv = 2 * Hk * D * BYTES * L
    bts = per_tok_kv * start + (H * D * BYTES * L) * n * 2 \
        + per_tok_kv * n * 2
    return flops, bts


def least_time(work: Iterable[Tuple[float, float]], peak: Dict) -> float:
    """Sum over units of work of max(flops / peak FLOP/s, bytes / peak
    bandwidth): the least time the chip could take for them."""
    return sum(max(f / peak["bf16_flops"], b / peak["hbm_bytes_s"])
               for f, b in work)


# ---------------------------------------------------------------------------
# The work of what the clients were served, inside the measured interval
# ---------------------------------------------------------------------------

def decode_contexts(ctx, until_s: float):
    """Context of every decode pass whose token reached its client between
    the window's start and ``until_s``, the lead-in's requests included:
    the j-th served token (from 0) of a request with a P-token prompt
    came from a pass over position P + j, which sees P + j + 1
    positions."""
    for r in ctx.served:
        for j, t in enumerate(r.token_s):
            if 0 <= t < until_s:
                yield r.prompt_len + j + 1


def prefilled_prompts(ctx, until_s: float):
    """Prompt length of every request whose first token reached its client
    between the window's start and ``until_s`` (its prefill is done)."""
    for r in ctx.served:
        if r.token_s and 0 <= r.token_s[0] < until_s:
            yield r.prompt_len
