"""Manual-collective MoE (shard_map all-to-all) vs the auto-partitioned
path, on 8 forced host devices in a subprocess.

MoE outputs can differ at individual tokens under ANY parallelism change
(router logit ties flip expert choice), so the check is: >=99% of tokens
match tightly and the aux loss agrees.
"""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.models import moe as moe_lib
    from repro.parallel import sharding as sh
    from repro.launch import mesh as mesh_lib

    cfg = get_config("qwen3-moe-235b-a22b").reduced()
    p = moe_lib.init_moe(cfg, jax.random.PRNGKey(0))
    x = (jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model),
                           jnp.float32) * 0.5).astype(jnp.bfloat16)

    class FM0:
        axis_names = ()
        devices = np.zeros((1,))
    sh.set_mesh_axis_sizes(FM0())
    ref, aux_ref = moe_lib.apply_moe(cfg, p, x)
    ref = np.asarray(ref, np.float32)

    mesh = mesh_lib.make_mesh((2, 4), ("data", "model"))
    sh.set_mesh_axis_sizes(mesh)
    assert moe_lib.manual_path_available(cfg, 4 * 32)
    with jax.set_mesh(mesh):
        out, aux = jax.jit(
            lambda p_, x_: moe_lib.apply_moe_manual(cfg, p_, x_))(p, x)
    out = np.asarray(out, np.float32)
    scale = np.abs(ref).max() + 1e-9
    tok_err = np.abs(out - ref).max(axis=-1) / scale
    frac_ok = (tok_err < 0.02).mean()
    assert frac_ok >= 0.99, frac_ok
    assert abs(float(aux) - float(aux_ref)) < 0.05
    print("MOE_MANUAL_OK", frac_ok)
""")


@pytest.mark.slow  # forced-8-device subprocess: multi-minute XLA compile
def test_moe_manual_matches_auto():
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
        cwd=__file__.rsplit("/tests", 1)[0], timeout=600)
    assert "MOE_MANUAL_OK" in r.stdout, r.stdout + r.stderr
