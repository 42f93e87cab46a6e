"""Jit-able step functions: train_step / prefill_step / serve_step.

These are the functions the dry-run lowers and the launchers execute.  All
distribution is expressed through in/out shardings assembled in
``shardings_for`` — the step bodies are mesh-agnostic.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, SHAPES, ShapeSpec
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.parallel import sharding
from repro.training import optimizer as opt_lib


def make_train_step(cfg: ModelConfig,
                    opt_cfg: Optional[opt_lib.AdamWConfig] = None):
    opt_cfg = opt_cfg or opt_lib.AdamWConfig()

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch))(params)
        params, opt_state, metrics = opt_lib.update(
            opt_cfg, grads, opt_state, params)
        metrics = dict(metrics, loss=loss)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, max_len: int):
    def prefill_step(params, batch):
        return M.prefill(cfg, params, batch, max_len)

    return prefill_step


def make_serve_step(cfg: ModelConfig):
    def serve_step(params, cache, tokens, position):
        return M.decode_step(cfg, params, cache, tokens, position)

    return serve_step


# ---------------------------------------------------------------------------
# Sharding assembly
# ---------------------------------------------------------------------------

def shardings_for(cfg: ModelConfig, shape_name: str, mesh):
    """(in_shardings, out_shardings) pytrees for the step of this shape.

    Axis state is scoped to this call (``sharding.use_axes``), not set
    process-globally: callers that later trace the step (e.g. dryrun's
    ``jit(...).lower``) do so under ``jax.set_mesh(mesh)``, which
    the constrain_* anchors fall back to."""
    from repro.launch import specs as specs_lib

    with sharding.use_axes(mesh):
        return _shardings_for(cfg, shape_name, mesh, specs_lib)


def _shardings_for(cfg: ModelConfig, shape_name: str, mesh, specs_lib):
    shape = SHAPES[shape_name]
    dp = mesh_lib.data_axes(mesh)
    ins = specs_lib.input_specs(cfg, shape_name)

    mode = "train" if shape.kind == "train" else "serve"
    pspec = sharding.param_specs(cfg, ins["params"], mode=mode)
    pspec = sharding.sanitize_specs(pspec, ins["params"])
    san = sharding.sanitize_specs
    ns = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        ospec = opt_lib.AdamWState(
            step=P(), m=san(pspec, ins["opt_state"].m),
            v=san(pspec, ins["opt_state"].v))
        bspec = san(sharding.batch_specs(cfg, ins["batch"], dp,
                                         shape.global_batch), ins["batch"])
        metrics_spec = {"grad_norm": P(), "lr": P(), "loss": P()}
        in_sh = (ns(pspec), ns(ospec), ns(bspec))
        out_sh = (ns(pspec), ns(ospec), ns(metrics_spec))
        return in_sh, out_sh
    if shape.kind == "prefill":
        bspec = san(sharding.batch_specs(cfg, ins["batch"], dp,
                                         shape.global_batch), ins["batch"])
        cshape = M.cache_specs(cfg, shape.global_batch, shape.seq_len)
        cspec = san(sharding.cache_specs(cfg, cshape, dp, shape.global_batch),
                    cshape)
        dpa = dp if shape.global_batch % sharding._axes_size_hint(dp) == 0 else None
        lshape = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.vocab_size), jnp.bfloat16)
        logits_spec = san(P(dpa, "model"), lshape)
        in_sh = (ns(pspec), ns(bspec))
        out_sh = (ns(logits_spec), ns(cspec))
        return in_sh, out_sh
    # decode
    cspec = san(sharding.cache_specs(cfg, ins["cache"], dp,
                                     shape.global_batch), ins["cache"])
    dpa = dp if shape.global_batch % sharding._axes_size_hint(dp) == 0 else None
    tok_spec = san(P(dpa, None), ins["tokens"])
    lshape = jax.ShapeDtypeStruct(
        (shape.global_batch, 1, cfg.vocab_size), jnp.bfloat16)
    logits_spec = san(P(dpa, None, "model"), lshape)
    in_sh = (ns(pspec), ns(cspec), ns(tok_spec), NamedSharding(mesh, P()))
    out_sh = (ns(logits_spec), ns(cspec))
    return in_sh, out_sh


def step_and_args(cfg: ModelConfig, shape_name: str):
    """(step_fn, abstract_args tuple) for lowering this cell."""
    from repro.launch import specs as specs_lib

    shape = SHAPES[shape_name]
    ins = specs_lib.input_specs(cfg, shape_name)
    if shape.kind == "train":
        return make_train_step(cfg), (ins["params"], ins["opt_state"],
                                      ins["batch"])
    if shape.kind == "prefill":
        return make_prefill_step(cfg, shape.seq_len), (ins["params"],
                                                       ins["batch"])
    return make_serve_step(cfg), (ins["params"], ins["cache"], ins["tokens"],
                                  ins["position"])
