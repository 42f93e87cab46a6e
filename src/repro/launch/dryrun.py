import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  * ``compiled.memory_analysis()``  — proves the step fits per-device HBM
  * roofline FLOPs / bytes / collective wire bytes

XLA's ``cost_analysis()`` counts each ``while`` body ONCE, independent of the
trip count (verified empirically), so a scan-over-layers program would be
undercounted by ~L.  We therefore lower each cell at several static depths and
extrapolate linearly:

    total(L) = f(0) + L * (f(1) - f(0))            (single layer stack)
    hybrid:  f(0) + G*(f(6)-f(0)) + T*(f(1)-f(0))  (G groups, T tail layers)
    audio:   f(00) + Le*(f(10)-f(00)) + Ld*(f(01)-f(00))

The same extrapolation is applied to collective wire bytes (collectives live
inside the layer body).  The chunked-attention inner loops (flash-style
blockwise softmax) are also while loops, so their body is counted once per
layer; we add their cost analytically (exact MAC counts + KV re-reads) via
``_attention_correction``.

Results are written as JSON under ``experiments/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Dict, Tuple

import jax

from repro.configs.base import SHAPES, get_config, list_archs
from repro.core import roofline
from repro.launch import mesh as mesh_lib, steps as steps_lib
from repro.models import model as M
from repro.models import layers as layers_lib

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


# ---------------------------------------------------------------------------
# Depth variants per family
# ---------------------------------------------------------------------------

def _variants(cfg) -> Dict[str, object]:
    """Map of label -> reduced-depth config used for extrapolation."""
    if cfg.family == "hybrid":
        per = cfg.hybrid.attn_every
        return {
            "f0": dataclasses.replace(cfg, num_layers=0),
            "f_tail": dataclasses.replace(cfg, num_layers=1),
            "f_group": dataclasses.replace(cfg, num_layers=per),
        }
    if cfg.family == "audio":
        ed = cfg.encdec
        return {
            "f0": dataclasses.replace(
                cfg, num_layers=0,
                encdec=dataclasses.replace(ed, num_encoder_layers=0)),
            "f_enc": dataclasses.replace(
                cfg, num_layers=0,
                encdec=dataclasses.replace(ed, num_encoder_layers=1)),
            "f_dec": dataclasses.replace(
                cfg, num_layers=1,
                encdec=dataclasses.replace(ed, num_encoder_layers=0)),
        }
    return {
        "f0": dataclasses.replace(cfg, num_layers=0),
        "f1": dataclasses.replace(cfg, num_layers=1),
    }


def _combine(cfg, meas: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """Linear-extrapolate per-device measurements to full depth.

    Slopes are clamped at zero: the partitioner occasionally picks a
    *cheaper* strategy for the deeper variant (e.g. skipping an all-gather
    the empty-stack program needs), and a negative slope would extrapolate
    to negative cost.
    """
    def lc(*terms):  # base + sum of L_i * max(meas[label_i] - base, 0)
        keys = set(meas["f0"].keys())
        for _, l in terms:
            keys |= set(meas[l].keys())
        out = {}
        for k in keys:
            base = meas["f0"].get(k, 0.0)
            out[k] = base + sum(
                L * max(meas[l].get(k, 0.0) - base, 0.0) for L, l in terms)
        return out

    if cfg.family == "hybrid":
        G = cfg.num_layers // cfg.hybrid.attn_every
        T = cfg.num_layers - G * cfg.hybrid.attn_every
        return lc((G, "f_group"), (T, "f_tail"))
    if cfg.family == "audio":
        Le, Ld = cfg.encdec.num_encoder_layers, cfg.num_layers
        return lc((Le, "f_enc"), (Ld, "f_dec"))
    return lc((cfg.num_layers, "f1"))


# ---------------------------------------------------------------------------
# Analytic correction for chunked-attention inner loops
# ---------------------------------------------------------------------------

def _attention_correction(cfg, shape) -> Tuple[float, float]:
    """(flops, bytes) global, for all blockwise-attention applications.

    Only full-sequence shapes use the chunked path (decode attention has no
    inner loop).  Counts: QK^T and PV MACs (causal halves self-attention),
    plus KV re-reads (each query block re-streams the full K and V).
    """
    if shape.is_decode:
        return 0.0, 0.0
    B = shape.global_batch
    H, D, Hk = cfg.num_heads, cfg.head_dim, cfg.num_kv_heads
    bpe = 2  # bf16

    def self_attn(S, n_apps, causal=True):
        if S < layers_lib.CHUNKED_ATTN_THRESHOLD:
            return 0.0, 0.0  # dense path: fully counted by cost_analysis
        frac = 0.5 if causal else 1.0
        flops = n_apps * 4.0 * B * H * S * S * D * frac
        nq = S // layers_lib.Q_CHUNK
        bytes_ = n_apps * nq * (2.0 * B * S * Hk * D * bpe)
        return flops, bytes_

    S = shape.seq_len
    train_mult = 3.0 if shape.kind == "train" else 1.0  # fwd + remat + bwd

    fam = cfg.family
    if fam in ("dense", "moe"):
        f, b = self_attn(S, cfg.num_layers)
    elif fam == "vlm":
        f, b = self_attn(S + cfg.num_patches, cfg.num_layers)
    elif fam == "hybrid":
        f, b = self_attn(S, cfg.num_layers // cfg.hybrid.attn_every)
    elif fam == "audio":
        f1, b1 = self_attn(S, cfg.num_layers, causal=True)
        f2, b2 = self_attn(cfg.encdec.encoder_seq_len,
                           cfg.encdec.num_encoder_layers, causal=False)
        f, b = f1 + f2, b1 + b2
    else:  # ssm: no attention
        f, b = 0.0, 0.0
    return f * train_mult, b * train_mult


# ---------------------------------------------------------------------------
# Single-cell measurement
# ---------------------------------------------------------------------------

def _measure(cfg, shape_name: str, mesh, want_memory: bool):
    """Lower+compile one config; return per-device cost dict (+mem, hlo)."""
    step, args = steps_lib.step_and_args(cfg, shape_name)
    in_sh, out_sh = steps_lib.shardings_for(cfg, shape_name, mesh)
    # Decode: donate the KV/state cache so XLA aliases it in place instead
    # of copying the full multi-GB cache every token.
    donate = (1,) if SHAPES[shape_name].is_decode else ()
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        mem = compiled.memory_analysis() if want_memory else None
    n_dev = mesh.devices.size
    coll = roofline.parse_collectives(hlo, n_dev)
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.wire_bytes / n_dev,  # per-device wire bytes
    }
    for op, v in coll.by_op.items():
        out[f"wire::{op}"] = v[2] / n_dev
        out[f"count::{op}"] = float(v[0])
    return out, mem, coll


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path,
             force: bool = False, kv_dtype: str = "bf16") -> dict:
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if kv_dtype == "bf16" else f"__kv{kv_dtype}"
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    if kv_dtype != "bf16":
        cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    shape = SHAPES[shape_name]
    ok, why = cfg.shape_supported(shape_name)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_name == "multi"))
    n_dev = mesh.devices.size
    t0 = time.time()
    try:
        # Full-depth compile: the sharding/memory proof.
        full_cost, mem, coll_full = _measure(cfg, shape_name, mesh,
                                             want_memory=True)
        t_full = time.time() - t0

        # Depth variants for cost extrapolation.
        meas = {}
        for label, vcfg in _variants(cfg).items():
            meas[label], _, _ = _measure(vcfg, shape_name, mesh,
                                         want_memory=False)
        total = _combine(cfg, meas)  # per-device
        af, ab = _attention_correction(cfg, shape)

        flops_g = total["flops"] * n_dev + af
        bytes_g = total["bytes"] * n_dev + ab
        wire_g = total["wire"] * n_dev

        n_params = M.param_count(cfg)
        n_active = M.param_count_active(cfg)
        tokens = shape.global_batch * (1 if shape.is_decode else shape.seq_len)
        mf = roofline.model_flops(n_active, tokens,
                                  train=(shape.kind == "train"))
        terms = roofline.RooflineTerms(
            flops=flops_g, bytes_hbm=bytes_g, wire_bytes=wire_g, chips=n_dev)

        mem_d = {}
        if mem is not None:
            for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                         "output_size_in_bytes", "alias_size_in_bytes",
                         "generated_code_size_in_bytes"):
                mem_d[attr] = getattr(mem, attr, None)

        coll_d = {}
        for key, val in sorted(total.items()):
            if key.startswith("wire::"):
                coll_d[key[6:]] = {
                    "wire_bytes_global": val * n_dev,
                    "count_per_layer_body": total.get(
                        "count::" + key[6:], 0.0),
                }

        rec.update(
            status="ok",
            devices=n_dev,
            compile_s=round(t_full, 1),
            total_s=round(time.time() - t0, 1),
            params=n_params,
            params_active=n_active,
            tokens=tokens,
            model_flops=mf,
            flops_hlo_global=flops_g,
            bytes_hlo_global=bytes_g,
            wire_bytes_global=wire_g,
            attention_correction={"flops": af, "bytes": ab},
            useful_flops_ratio=(mf / flops_g) if flops_g else None,
            memory_analysis=mem_d,
            collectives=coll_d,
            roofline=terms.as_dict(),
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def run_serving_smoke(out_dir: Path, model_parallel: int = 2,
                      requests: int = 4, max_new: int = 8) -> dict:
    """Multi-host serving smoke: sharded-engine vs solo-engine parity.

    Uses the forced 512-device host platform this module already runs
    under, but builds a SMALL (1, model_parallel) submesh over the first
    few devices (compiling against all 512 would take minutes for a
    smoke).  A reduced engine with the paged pool sharded over ``model``
    must emit greedy tokens identical to the meshless engine — float32
    params so TP psum reduction-order noise cannot flip an argmax — with
    bitwise-identical scheduler stats.  Writes serving_smoke.json.
    """
    import numpy as np

    from repro.serving.engine import ServingEngine

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              num_heads=4, num_kv_heads=4)
    params = jax.tree.map(lambda x: x.astype(jax.numpy.float32),
                          M.init_params(cfg, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
               for n in rng.integers(4, 14, size=requests)]

    def run(mesh):
        eng = ServingEngine(cfg, params, max_batch=4, max_len=48,
                            mode="continuous", mesh=mesh, block_size=8,
                            prefill_chunk=8, seed=7)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        t0 = time.time()
        out = eng.run()
        return out, time.time() - t0, eng.stats

    solo, solo_s, s0 = run(None)
    devs = np.array(jax.devices()[:model_parallel]).reshape(
        1, model_parallel)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    shard, shard_s, s1 = run(mesh)
    rec = {
        "status": "ok" if solo == shard else "error",
        "devices": model_parallel,
        "model_parallel": model_parallel,
        "requests": requests,
        "greedy_identical": solo == shard,
        "stats_identical": (s0.preemptions, s0.admissions,
                            s0.cached_prompt_tokens)
        == (s1.preemptions, s1.admissions, s1.cached_prompt_tokens),
        "solo_wall_s": round(solo_s, 3),
        "sharded_wall_s": round(shard_s, 3),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"serving_smoke__mp{model_parallel}.json"
    path.write_text(json.dumps(rec, indent=2))
    return rec


def cells(mesh_sel: str):
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[mesh_sel]
    for arch in list_archs():
        for shape_name in SHAPES:
            for m in meshes:
                yield arch, shape_name, m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--kv-dtype", default="bf16", choices=["bf16", "f8"])
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--serving-smoke", action="store_true",
                    help="run the multi-host serving parity smoke (a "
                         "sharded reduced engine vs the meshless one) "
                         "instead of the compile sweep")
    ap.add_argument("--model-parallel", type=int, default=2,
                    help="[--serving-smoke] model-axis width of the "
                         "submesh the sharded engine runs on")
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.serving_smoke:
        rec = run_serving_smoke(out_dir,
                                model_parallel=args.model_parallel)
        print(json.dumps(rec, indent=2))
        return 0 if rec["status"] == "ok" else 1

    if args.all:
        todo = list(cells(args.mesh))
    else:
        assert args.arch and args.shape
        meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
        todo = [(args.arch, args.shape, m) for m in meshes]

    n_ok = n_skip = n_err = 0
    for arch, shape_name, mesh_name in todo:
        rec = run_cell(arch, shape_name, mesh_name, out_dir,
                       force=args.force, kv_dtype=args.kv_dtype)
        st = rec["status"]
        n_ok += st == "ok"
        n_skip += st == "skipped"
        n_err += st == "error"
        extra = ""
        if st == "ok":
            r = rec["roofline"]
            extra = (f"total={rec['total_s']}s bottleneck={r['bottleneck']} "
                     f"t=({r['t_compute_s']:.2e},{r['t_memory_s']:.2e},"
                     f"{r['t_collective_s']:.2e})s "
                     f"useful={rec['useful_flops_ratio']:.2f}"
                     if rec.get("useful_flops_ratio") else "")
        elif st == "error":
            extra = rec["error"][:160]
        print(f"[{st:7s}] {arch:24s} {shape_name:12s} {mesh_name:6s} {extra}",
              flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} errors={n_err}")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
