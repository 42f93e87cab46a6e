"""Sharding rules: parameter, cache, batch and optimizer-state PartitionSpecs.

Baseline layout (the "paper-faithful" mapping — tensor parallelism over
``model``, fully-sharded (FSDP/ZeRO-3 style) parameter+optimizer storage over
``data``, replication over ``pod``):

  * attention/MLP weights: 2-D sharded (fan-in over one axis, fan-out over the
    other) — this is the 2-D weight-stationary layout of Pope et al. [37] that
    the paper adopts for the feed-forward network;
  * MoE expert tensors: expert dim over ``model``, expert hidden dim over
    ``data`` (expert parallelism × tensor parallelism);
  * KV caches: batch over data axes, sequence over ``model`` (split-KV decode:
    each model shard owns a contiguous stripe of the context);
  * SSM states: batch over data axes, heads over ``model``.

Rules are path-based so they apply to scan-stacked parameters (leading layer
dims map to None).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec


# ---------------------------------------------------------------------------
# Mesh-axis state (context-scoped, not process-global)
#
# The spec rules and the constrain_* anchors below need to know the active
# mesh's axis sizes at TRACE time.  This used to be a trio of module globals
# mutated by ``set_mesh_axis_sizes`` — which meant one serving mesh per
# process and stale state leaking between components.  The state now lives in
# a ``ContextVar``:
#
#   * ``use_axes(mesh)`` scopes it to a ``with`` block — the serving engine
#     wraps its jitted-function bodies in this, so every engine traces under
#     its OWN mesh regardless of what the rest of the process is doing;
#   * ``set_mesh_axis_sizes(mesh)`` sets it for the current context
#     (scripts / tests that want ambient state);
#   * when nothing was set explicitly, readers fall back to the mesh active
#     in the enclosing jax context (``jax.set_mesh``), so
#     ``jit(...).lower()`` under ``jax.set_mesh`` sees the right axes without
#     any global hand-off.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AxisState:
    """Immutable snapshot of a mesh's (axis name, size) pairs."""
    sizes: Tuple[Tuple[str, int], ...] = ()

    @classmethod
    def from_mesh(cls, mesh) -> "AxisState":
        if mesh is None:
            return cls()
        try:
            names, shape = tuple(mesh.axis_names), tuple(mesh.devices.shape)
        except AttributeError:  # AbstractMesh: no .devices
            names, shape = tuple(mesh.axis_names), \
                tuple(mesh.shape[a] for a in mesh.axis_names)
        return cls(tuple(zip(names, shape)))

    def size(self, name: Optional[str]) -> int:
        return dict(self.sizes).get(name, 1) if name else 1

    @property
    def dp(self) -> Tuple[str, ...]:
        names = [a for a, _ in self.sizes]
        return tuple(a for a in ("pod", "data") if a in names)

    @property
    def tp(self) -> Optional[str]:
        return "model" if any(a == "model" for a, _ in self.sizes) else None


#: None = nothing explicitly set in this context -> fall back to the ambient
#: jax mesh; an explicit (possibly empty) AxisState always wins.
_AXIS_STATE: "contextvars.ContextVar[Optional[AxisState]]" = \
    contextvars.ContextVar("mesh_axis_state", default=None)


def axis_state() -> AxisState:
    """The axis state readers resolve: explicit context state, else the
    enclosing jax mesh context, else empty (no sharding anchors)."""
    st = _AXIS_STATE.get()
    if st is not None:
        return st
    m = current_mesh()
    return AxisState.from_mesh(m) if m is not None else AxisState()


def set_mesh_axis_sizes(mesh) -> None:
    """Set the axis state for the CURRENT context (script/test ambient use;
    pass an empty-axes mesh to clear).  Engine code should prefer the scoped
    ``use_axes``."""
    _AXIS_STATE.set(AxisState.from_mesh(mesh))


@contextlib.contextmanager
def use_axes(state) -> Iterator[AxisState]:
    """Scope the axis state to a ``with`` block.  ``state`` is an AxisState
    or a mesh (None = explicitly no axes, shadowing any ambient state)."""
    if not isinstance(state, AxisState):
        state = AxisState.from_mesh(state)
    token = _AXIS_STATE.set(state)
    try:
        yield state
    finally:
        _AXIS_STATE.reset(token)


def axis_size(name: Optional[str]) -> int:
    return axis_state().size(name)


def data_axes() -> Tuple[str, ...]:
    """Batch-sharding axes ("pod"/"data") present in the active mesh."""
    return axis_state().dp


def tp_axis() -> Optional[str]:
    """The tensor-parallel axis ("model") if the active mesh has one."""
    return axis_state().tp


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _param_rule(cfg: ModelConfig, path: str, ndim: int, mode: str,
                fsdp: str = "data", tp: str = "model") -> P:
    """Spec for one parameter given its path and rank.

    mode="train": FSDP (ZeRO-3) over ``data`` x TP over ``model`` — weights
    are 2-D sharded and all-gathered per layer inside the step; optimizer
    state stays fully sharded.

    mode="serve": weights live resident (no per-token regather): TP over
    ``model`` only, except MoE expert tensors which use expert parallelism
    over ``data`` x TP over ``model`` — the expert dim is batch-like in the
    expert einsum so no gather is induced.

    The rank-suffix convention: rules name the trailing dims; leading stacked
    layer/group dims are padded with None.
    """
    serve = mode == "serve"
    fs = None if serve else fsdp

    def pad(*spec):
        return P(*([None] * (ndim - len(spec)) + list(spec)))

    leaf = path.rsplit("/", 1)[-1]
    tp_n = axis_size(tp)
    vocab_ok = cfg.vocab_size % tp_n == 0

    # Embedding / unembedding. When the vocab doesn't divide the model axis
    # (e.g. mamba2's 50280), shard the d_model dim instead.
    if leaf == "embed":
        if vocab_ok:
            return P(tp, fs)
        return P(None, tp if serve else fsdp)
    if leaf == "lm_head":
        if vocab_ok:
            return P(fs, tp)
        return P(tp if serve else fsdp, None)
    if leaf == "patch_proj":
        return P(fs, tp)

    # Norm scales/biases: replicated (small).
    if leaf in ("scale", "bias", "conv_b", "A_log", "D", "dt_bias"):
        return pad(None)
    if leaf == "norm_scale":
        return pad(tp)

    # Attention projections.
    if leaf in ("wq", "wk", "wv"):
        return pad(fs, tp)
    if leaf == "wo":
        return pad(tp, fs)
    if leaf in ("bq", "bk", "bv"):
        return pad(tp)

    # Dense / shared-expert MLP.
    if leaf in ("w_gate", "w_up", "w_down"):
        if "moe" in path and "shared" not in path:
            # Expert-stacked: (..., E, d, f) or (..., E, f, d).
            # Expert parallelism over ``data`` x TP, in BOTH modes: the
            # expert dim is batch-like (never gathered) and storage is
            # 256-way sharded.  When the manual-collective path applies
            # (E divides the data axis), TP splits *d_model* so the MoE
            # all-to-alls carry d/tp-sliced payloads and the up-projection
            # psum runs at h-volume (see moe.apply_moe_manual); otherwise
            # TP splits the hidden dim (plain Megatron-in-expert).
            ep_n = axis_size(fsdp)
            d_layout = cfg.moe is not None and ep_n > 1 \
                and cfg.moe.num_experts % ep_n == 0
            if d_layout:
                if leaf == "w_down":
                    return pad(fsdp, None, tp)
                return pad(fsdp, tp, None)
            if leaf == "w_down":
                return pad(fsdp, tp, None)
            return pad(fsdp, None, tp)
        if leaf == "w_down":
            return pad(tp, fs)
        return pad(fs, tp)
    if leaf == "router":
        return pad(fs, None)

    # Mamba2.
    if leaf == "in_proj":
        return pad(fs, tp)
    if leaf == "conv_w":
        return pad(None, tp)
    if leaf == "out_proj":
        return pad(tp, fs)

    return pad(None)


def param_specs(cfg: ModelConfig, params_shape, mode: str = "train") -> Any:
    """PartitionSpec pytree matching params (or their ShapeDtypeStructs)."""
    def rule(path, leaf):
        return _param_rule(cfg, _path_str(path), len(leaf.shape), mode)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


# ---------------------------------------------------------------------------
# Cache specs
# ---------------------------------------------------------------------------

def cache_specs(cfg: ModelConfig, cache_shape, dp: Optional[Tuple[str, ...]],
                batch: int, tp: str = "model", paged: bool = False) -> Any:
    """dp = batch axes (None to replicate small batches).

    paged: the k/v leaves are lane-dense block pools (L, N, bs, Hk*hd)
    rather than dense (L, B, S, Hk, hd) stripes — any request's block
    table may point anywhere in the pool, so the pool is NOT
    batch-shardable; shard the KV heads over ``model`` instead (contiguous
    head ranges of the merged lane axis, matching the decode attention TP
    layout), and only where ``model`` divides Hk — the gate
    ``attn_shard_size`` applies at dispatch.
    """
    dpa = dp if (dp and batch % _axes_size_hint(dp) == 0) else None
    heads_tp = tp if paged and cfg.num_kv_heads % axis_size(tp) == 0 \
        else None

    def rule(path, leaf):
        nd = len(leaf.shape)
        path_s = _path_str(path)
        name = path_s.rsplit("/", 1)[-1]
        if name in ("k", "v", "attn_k", "attn_v", "cross_k", "cross_v"):
            if paged:
                return P(None, None, None, heads_tp)
            # (L, B, S, Hk, hd): batch over dp, sequence over model.
            return P(None, dpa, tp, None, None)
        if name in ("k_scale", "v_scale") and paged:
            # SCLAD scale metadata (L, N, bs, Hk): co-sharded with the
            # payload's KV heads so each shard dequantizes locally.
            return P(None, None, None, heads_tp)
        if name == "state":
            # (..., B, H, P, N): heads over model.
            return P(*([None] * (nd - 4)), dpa, tp, None, None)
        if name == "conv":
            # (..., B, k-1, conv_dim): channels over model.
            return P(*([None] * (nd - 3)), dpa, None, tp)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def _axes_size_hint(axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------

def batch_specs(cfg: ModelConfig, batch_shape, dp: Optional[Tuple[str, ...]],
                batch: int) -> Any:
    dpa = dp if (dp and batch % _axes_size_hint(dp) == 0) else None

    def rule(path, leaf):
        nd = len(leaf.shape)
        return P(dpa, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_shape)


def sanitize_specs(spec_tree, shape_tree) -> Any:
    """Drop sharding on any dim the mesh axis doesn't divide evenly.

    jax.jit argument shardings require exact divisibility; internal
    with_sharding_constraint does not.  This keeps rules simple and fixes up
    the stragglers (60 experts, 50280 vocab, batch 1, seq 1500, ...).
    """
    def fix(spec, leaf):
        dims = leaf.shape
        out = []
        for i, axes in enumerate(tuple(spec) + (None,) * (len(dims) - len(spec))):
            if axes is None:
                out.append(None)
                continue
            axes_t = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for a in axes_t:
                size *= axis_size(a)
            out.append(axes if dims[i] % size == 0 else None)
        return P(*out)

    return jax.tree.map(fix, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def to_shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Serving-side shard_map helpers (the paged-attention dispatch wrappers in
# kernels/flash_decode/ops.py and kernels/flash_prefill/ops.py)
# ---------------------------------------------------------------------------

#: The tensor-parallel mesh axis every serving-side rule shards over.
TP_AXIS = "model"


def attn_shard_size(mesh, num_kv_heads: int, axis: str = TP_AXIS) -> int:
    """How many ways the paged attention dispatch can shard the KV-head axis.

    The shard_map wrappers split the (L, N, bs, Hk*D) pool's heads —
    payload AND SCLAD scale leaves — plus the query head groups over
    ``axis``, with everything host-derived (block tables, length/start
    vectors, the layer index — the kernels' scalar-prefetch operands)
    broadcast.  Returns 1 (single-device dispatch, no wrapper) when there
    is no mesh, the mesh has no ``axis`` (or it is trivial), or
    ``num_kv_heads`` does not divide it evenly — exactly the cases
    ``cache_specs(paged=True)`` leaves the pool's heads unsharded for, so
    cache placement and kernel dispatch always agree.
    """
    if mesh is None:
        return 1
    m = AxisState.from_mesh(mesh).size(axis)
    return m if m > 1 and num_kv_heads % m == 0 else 1


def paged_attn_specs(axis: str = TP_AXIS) -> Dict[str, P]:
    """PartitionSpecs for the paged-attention shard_map wrappers.

    Head-axis sharding is contiguous, so a shard's Hk/m KV heads arrive
    with ALL of their ``rep = H // Hk`` query heads (queries are laid out
    head-major) — the per-shard kernel body is the unchanged single-device
    kernel on a contiguous head slice.  The pool's merged (Hk*D) lane axis
    is head-major too, so its m contiguous lane ranges are exactly those
    head slices.  ``out_chunk`` is the prefill
    output AFTER its (B, S, H, D) -> (B, S, H*D) head-major flatten, so
    concatenating shards on the last axis restores the full head order.
    """
    return {
        "q_decode": P(None, axis, None),        # (B, H, D) head groups
        "q_chunk": P(None, None, axis, None),   # (B, S, H, D)
        "new_kv": P(None, None, axis, None),    # (B, S, Hk, D) chunk K/V
        "pool": P(None, None, None, axis),      # (L, N, bs, Hk*D)
        "scale": P(None, None, None, axis),     # (L, N, bs, Hk) SCLAD scales
        "host": P(),                            # tables/lengths/starts/layer
        "out_decode": P(None, axis, None),      # (B, H, D)
        "out_chunk": P(None, None, axis),       # (B, S, H*D) head-major
    }


def current_mesh():
    """The mesh active in the enclosing context (``jax.set_mesh``), or
    None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def constrain(x, spec: P):
    """with_sharding_constraint that is a no-op outside a mesh context."""
    if current_mesh() is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


SEQUENCE_PARALLEL = True


def _seq_shardable(x, st: AxisState) -> bool:
    """Sequence-parallel residuals (Korthikanti et al.): between blocks the
    (B, S, d) stream is sharded over `model` along S, so saved-for-backward
    activations cost 1/tp the HBM and the TP all-reduce becomes a
    reduce-scatter + all-gather pair (half the wire bytes)."""
    if not SEQUENCE_PARALLEL or st.tp is None or x.ndim < 3:
        return False
    tp_n = st.size(st.tp)
    return tp_n > 1 and x.shape[1] % tp_n == 0 and x.shape[1] > 1


def constrain_tokens(x):
    """Anchor a (B, S, d) activation: batch over data axes; S over model
    when sequence parallelism applies (never for single-token decode)."""
    st = axis_state()
    if not st.dp:
        return x
    seq = st.tp if _seq_shardable(x, st) else None
    return constrain(x, P(st.dp, seq, *([None] * (x.ndim - 2))))


def constrain_logits(x):
    """Anchor (B, S, V) logits: batch over data; S over model when
    sequence-parallel (keeps the fp32 loss buffer sharded), else vocab."""
    st = axis_state()
    if not st.dp:
        return x
    if _seq_shardable(x, st):
        return constrain(x, P(st.dp, st.tp,
                              *([None] * (x.ndim - 2))))
    return constrain(x, P(st.dp, *([None] * (x.ndim - 2)), st.tp))


def constrain_heads(x):
    """Anchor a (B, S, H, D) attention tensor: batch over data, heads TP."""
    st = axis_state()
    if not st.dp:
        return x
    return constrain(x, P(st.dp, None, st.tp, None))
