"""A whole run on the CPU at a tiny size, past the look for a chip: sound,
with the timed path broken underneath, and with the control (the
reference in fp8) in the program's place.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import numpy as np
import pytest

import tiny
import run as R

SEED = 2**31 + 4242
SECONDS = 2.0


def _run(cell, break_path=None, control=None):
    return R.run(cell, SEED, SECONDS, False, break_path=break_path,
                 control=control, peaks=tiny.PEAKS)


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_sound_run_is_correct(kernel):
    out = _run(tiny.tiny_cell(kernel))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 5
    assert list(out)[-1] == "checks"


def _alter_tokens(eng):
    """A token altered where it is produced: every sampled token is
    shifted by one."""
    import repro.serving.engine as E
    orig = E.sample
    vocab = eng.cfg.vocab_size
    E.sample = lambda *a, **k: (orig(*a, **k) + 1) % vocab
    return orig


def _drop_kv_writes(eng):
    """A step that returns its state unchanged: decode leaves the pool as
    it found it."""
    import repro.models.model as M
    orig = M.decode_step

    def stale(cfg, params, cache, *a, **k):
        logits, _ = orig(cfg, params, cache, *a, **k)
        return logits, cache

    M.decode_step = stale
    return orig


@pytest.mark.parametrize("kernel", ["off", "on"])
@pytest.mark.parametrize("fault,module,name", [
    (_alter_tokens, "repro.serving.engine", "sample"),
    (_drop_kv_writes, "repro.models.model", "decode_step"),
])
def test_broken_path_is_not_correct(fault, module, name, kernel):
    import importlib
    mod = importlib.import_module(module)
    saved = {}
    try:
        out = _run(tiny.tiny_cell(kernel),
                   break_path=lambda eng: saved.setdefault(
                       "orig", fault(eng)))
    finally:
        setattr(mod, name, saved["orig"])
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("limit", sorted(tiny.cell_limits().items()))
@pytest.mark.parametrize("kernel", ["off", "on"])
def test_control_fails_the_limit(kernel, limit):
    """The reference in fp8 put in the program's place: a whole run, judged
    at each chip configuration's limit, is not correct."""
    out = _run(tiny.tiny_cell(kernel, limit[1]), control="fp8")
    gap = out["checks"]["max_logit_gap"]
    print("control", gap)
    assert not out["correct"] and np.isfinite(gap["value"])
