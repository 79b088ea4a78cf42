"""Small configurations for the benchmark's CPU self-checks.

Run from the repository root:  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=96,
            num_experts=8, vocab_size=300, max_position_embeddings=64)


def _load(*parts):
    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


@pytest.fixture
def tiny_m6():
    mc = _load("configs", "m6-base.json")
    mc.update(TINY, num_image_tokens=4, group_size=32, warmup_steps=2,
              dtype="float32")
    return mc


@pytest.fixture
def tiny_olmoe():
    mc = _load("configs", "olmoe-1b-7b-4l.json")
    mc.update(TINY, num_experts_per_tok=3, dtype="float32")
    return mc


@pytest.fixture
def tiny_backlog():
    t = _load("traffic", "batch-short.json")
    t.update(prompt={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
             output={"median": 8, "sigma": 0.5, "min": 2, "max": 16},
             max_total=32, block=8, requests=20000, warmup_finished=4,
             engine={"max_slots": 4, "kv_block_size": 8, "prefill_chunk": 8,
                     "max_len": 32, "num_blocks": 16},
             check={"tokens_min": 24, "requests_max": 8})
    return t


@pytest.fixture
def tiny_pretrain():
    t = _load("traffic", "pretrain.json")
    t.update(batch=4, text_len=12, warmup_steps=2)
    return t


MANIFEST = {"end_to_end": [{"name": "setup_s", "unit": "s"}], "per_layer": []}
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
