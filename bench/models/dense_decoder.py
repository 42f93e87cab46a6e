"""Plain reference of a pre-norm dense decoder, and the weights that the
benchmark serves it with.

Covers StableLM 2 (LayerNorm with bias, partial rotary, q/k/v biases,
multi-head attention, untied head) and Granite 3.0 (RMSNorm, grouped-query
attention, tied embeddings, scalar multipliers), as the configuration
file's ``model`` section states them in the published config's own keys.

Written from the published equations in straightforward ``jax.numpy``,
float32 under ``default_matmul_precision("highest")``; it imports nothing
of the program.  ``precision="fp8"`` rounds every matrix product's
operands to float8 e4m3 (per-row scales) instead: the control, one step of
precision below the bfloat16 that the configuration serves.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

#: Query rows per attention block in the reference (bounds the score
#: matrix at heads x 512 x sequence).
Q_BLOCK = 512
#: Reference sequences are padded to the next of these lengths, and the
#: positions read to the next of ROWS, so that few reference programs
#: compile; causal masking keeps the padding out of every real position.
SEQ = (1024, 2048, 4096)
ROWS = (128, 1024)


def dims(model: Dict) -> Dict:
    """The sizes and switches of ``model`` (published config keys)."""
    d = model["hidden_size"]
    heads = model["num_attention_heads"]
    hd = model.get("head_dim") or d // heads
    rot = int(hd * model.get("partial_rotary_factor", 1.0))
    layernorm = "layer_norm_eps" in model
    return dict(
        layers=model["num_hidden_layers"], d=d, heads=heads,
        kv_heads=model["num_key_value_heads"], head_dim=hd,
        ff=model["intermediate_size"], vocab=model["vocab_size"],
        rot=rot - rot % 2, theta=float(model["rope_theta"]),
        layernorm=layernorm,
        eps=model["layer_norm_eps"] if layernorm else model["rms_norm_eps"],
        qkv_bias=bool(model.get("use_qkv_bias",
                                model.get("attention_bias", False))),
        tied=bool(model.get("tie_word_embeddings", False)),
        emb_mult=float(model.get("embedding_multiplier", 1.0)),
        attn_mult=float(model.get("attention_multiplier",
                                  1.0 / math.sqrt(hd))),
        res_mult=float(model.get("residual_multiplier", 1.0)),
        logit_div=float(model.get("logits_scaling", 1.0)),
    )


# ---------------------------------------------------------------------------
# Weights: made on the device from the seed, in one jitted call, in the
# type they are served in.
# ---------------------------------------------------------------------------

def _shapes(m: Dict) -> Dict:
    d, L, H, Hk, D, F, V = (m[k] for k in (
        "d", "layers", "heads", "kv_heads", "head_dim", "ff", "vocab"))
    norm = lambda: {"scale": (L, d), "bias": (L, d)} if m["layernorm"] \
        else {"scale": (L, d)}
    attn = {"wq": (L, d, H * D), "wk": (L, d, Hk * D), "wv": (L, d, Hk * D),
            "wo": (L, H * D, d)}
    if m["qkv_bias"]:
        attn.update(bq=(L, H * D), bk=(L, Hk * D), bv=(L, Hk * D))
    s = {"embed": (V, d),
         "final_norm": {k: v[1:] for k, v in norm().items()},
         "blocks": {"ln_attn": norm(), "attn": attn, "ln_mlp": norm(),
                    "mlp": {"w_gate": (L, d, F), "w_up": (L, d, F),
                            "w_down": (L, F, d)}}}
    if not m["tied"]:
        s["lm_head"] = (d, V)
    return s


def _draw(key, path: str, shape) -> jnp.ndarray:
    """One leaf: projections at 1/sqrt(fan-in), embeddings at 0.02, norm
    scales about 1 and biases about 0 (not exactly, so that a dropped
    scale or bias shows)."""
    z = jax.random.normal(key, shape, jnp.float32)
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        x = 1.0 + 0.1 * z
    elif leaf in ("bias", "bq", "bk", "bv"):
        x = 0.05 * z
    elif leaf == "embed":
        x = 0.02 * z
    else:
        x = z / math.sqrt(shape[-2])
    return x.astype(jnp.bfloat16)


def make_weights(model: Dict, seed: int):
    """Every weight of the configuration, bfloat16 on the default device,
    from ``seed``, in the program's parameter layout."""
    m = dims(model)
    shapes = _shapes(m)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            tree, [_draw(k, p, s) for k, p, (_, s) in zip(keys, paths, flat)])

    key = jax.random.fold_in(jax.random.PRNGKey(0), seed % (2**31))
    key = jax.random.fold_in(key, seed // (2**31))
    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# Reference forward
# ---------------------------------------------------------------------------

def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, fp8: bool):
    """a (..., k) @ b (k, n): float32, or with both operands in fp8."""
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return a @ b


def _norm(m, x, p):
    if m["layernorm"]:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m["eps"]) * p["scale"] + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + m["eps"]) \
        * p["scale"]


def _rope(m, x, pos):
    """Rotate-half rotary embedding on the first ``rot`` dims of each
    head; x (S, H, D)."""
    rot = m["rot"]
    if rot == 0:
        return x
    inv = 1.0 / m["theta"] ** (jnp.arange(0, rot, 2, dtype=jnp.float32)
                               / rot)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _attention(m, q, k, v, fp8: bool):
    """Causal attention; q (S, H, D), k/v (S, Hk, D); query blocks of
    Q_BLOCK rows."""
    S, H, D = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 0)
    out = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) * m["attn_mult"]
        mask = (lo + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        if fp8:
            p = _fp8(p, -1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out, axis=0).reshape(S, H * D)


def _layer(m, fp8, x, w):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    S = x.shape[0]
    pos = jnp.arange(S)
    H, Hk, D = m["heads"], m["kv_heads"], m["head_dim"]
    a = w["attn"]
    h = _norm(m, x, w["ln_attn"])
    q, k, v = (_mm(h, a[n], fp8) for n in ("wq", "wk", "wv"))
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(m, q.reshape(S, H, D), pos)
    k = _rope(m, k.reshape(S, Hk, D), pos)
    o = _attention(m, q, k, v.reshape(S, Hk, D), fp8)
    x = x + m["res_mult"] * _mm(o, a["wo"], fp8)
    h = _norm(m, x, w["ln_mlp"])
    f = w["mlp"]
    g = jax.nn.silu(_mm(h, f["w_gate"], fp8)) * _mm(h, f["w_up"], fp8)
    return x + m["res_mult"] * _mm(g, f["w_down"], fp8), None


def _logits_fn(m, fp8: bool):
    def fn(weights, tokens, rows):
        """tokens (S,) -> logits (len(rows), vocab) at positions rows."""
        x = weights["embed"][tokens].astype(jnp.float32) * m["emb_mult"]
        x, _ = jax.lax.scan(lambda c, w: _layer(m, fp8, c, w), x,
                            weights["blocks"])
        fn_w = jax.tree.map(lambda a: a.astype(jnp.float32),
                            weights["final_norm"])
        h = _norm(m, x[rows], fn_w)
        head = weights["embed"].T if m["tied"] else weights["lm_head"]
        return _mm(h, head.astype(jnp.float32), fp8) / m["logit_div"]
    return jax.jit(fn)


#: One jitted reference per (sizes, precision).
_FNS: Dict = {}


def logits_at(model: Dict, weights, tokens: np.ndarray, rows: np.ndarray,
              precision: str = "f32") -> np.ndarray:
    """Reference logits of the sequence ``tokens`` at positions ``rows``
    (each predicts the token after it), as a float32 numpy array.  The
    sequence is padded at its end to the next of SEQ and ``rows`` to the
    next of ROWS, so that few programs compile."""
    m = dims(model)
    key = (tuple(sorted(m.items())), precision)
    if key not in _FNS:
        _FNS[key] = _logits_fn(m, precision == "fp8")
    S = next(x for x in SEQ + (len(tokens),) if x >= len(tokens))
    n = next(x for x in ROWS + (len(rows),) if x >= len(rows))
    tok = np.zeros(S, np.int32)
    tok[:len(tokens)] = tokens
    r = np.zeros(n, np.int32)
    r[:len(rows)] = rows
    with jax.default_matmul_precision("highest"):
        out = _FNS[key](weights, jnp.asarray(tok), jnp.asarray(r))
    return np.asarray(out, np.float32)[:len(rows)]
