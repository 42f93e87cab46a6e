"""A cell at a size the CPU runs in seconds: the same harness, engine and
reference as the chip cells, with a two-layer StableLM-shaped model."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from lib.spec import Cell  # noqa: E402

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
         "hbm_bytes_s": 819e9}


def cell_limits() -> dict:
    """Each chip configuration's limit on the widest logit gap."""
    out = {}
    for path in sorted((BENCH / "configs").glob("*.json")):
        with open(path) as f:
            out[path.stem] = json.load(f)["correctness"]["max_logit_gap"]
    return out


def tiny_cell(kernel: str = "off", limit: float = None) -> Cell:
    """The tiny cell, judged at ``limit`` (stablelm-1.6b's by default)."""
    with open(BENCH / "configs" / "stablelm-1.6b.json") as f:
        conf = json.load(f)
    conf = copy.deepcopy(conf)
    conf["model"].update(num_hidden_layers=2, hidden_size=64,
                         num_attention_heads=4, num_key_value_heads=4,
                         intermediate_size=128, vocab_size=256)
    conf["overrides"] = dict(qkv_bias=True, num_layers=2, d_model=64,
                             num_heads=4, num_kv_heads=4, head_dim=16,
                             d_ff=128, vocab_size=256)
    conf["engine"]["settings"].update(max_batch=4, max_len=128,
                                      block_size=8, num_blocks=48,
                                      prefill_chunk=32, attn_kernel=kernel)
    conf["correctness"].update(min_tokens=48, max_requests=6)
    if limit is not None:
        conf["correctness"]["max_logit_gap"] = limit
    mix = {"loop": "open", "rate_req_s": 6.0, "lead_s": 0.5, "drain_s": 60,
           "prompt": {"dist": "lognormal", "median": 30, "sigma": 0.6,
                      "min": 9, "max": 90},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                      "min": 2, "max": 30}}
    w = {"name": "tiny.open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "CPU test"}
    return Cell(w, conf, mix, [], [])
