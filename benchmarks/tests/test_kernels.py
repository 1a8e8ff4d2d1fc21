"""Each kernel's law against a hand count, the per-token law against the
parameter counts of the two configurations, the peaks table."""

import json
from pathlib import Path

import pytest

from benchmarks import peaks
from benchmarks.harness import loader

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def hf(name):
    return loader.hf_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_expert_mlp_law_by_hand():
    k = loader.load_module("kernels", "expert_mlp")
    # one routed row: gate 2*2048*768, up the same, down 2*768*2048
    assert k.forward_flops(1, 2048, 768) == 3 * 2 * 2048 * 768
    assert k.train_flops(8192, 8, 2048, 768) == 3 * 8192 * 8 * 6 * 2048 * 768
    import re
    assert re.search(k.TRACE_PATTERN, "fused_expert_mlp_bwd_dwd.2")
    assert re.search(k.TRACE_PATTERN, "gmm.8") and re.search(k.TRACE_PATTERN, "tgmm")
    assert not re.search(k.TRACE_PATTERN, "fusion.36")
    assert re.search(k.FWD_PATTERN, "fused_expert_mlp_fwd") and not re.search(k.FWD_PATTERN, "fused_expert_mlp_bwd_gu.2")


def test_flash_attention_law_by_hand():
    k = loader.load_module("kernels", "flash_attn")
    # 4 positions: 10 (query, key) pairs; QK^T and PV, 2 ops a MAC, 8 channels, 2 heads
    assert k.forward_flops(1, 4, 2, 8) == 10 * 2 * 2 * 8 * 2
    assert k.train_flops(1, 4, 2, 8) == 3 * k.forward_flops(1, 4, 2, 8)


def test_paged_attention_law_by_hand():
    k = loader.load_module("kernels", "paged_attn")
    # MiniMax-M2, one layer: 8 KV heads x 128 x (K and V) x 2 bytes = 4096 B a token
    assert k.kv_bytes(1, 1, 8, 128) == 4096
    assert k.kv_bytes(524288, 1, 8, 128) == 2 * 1024**3


def test_per_token_law_against_the_parameter_counts():
    k = loader.load_module("kernels", "model_flops")
    sdar, mm = hf("sdar-30b-a3b.train-l1"), hf("minimax-m2.serve-l1")
    assert k.parameter_count(sdar) == pytest.approx(1.245e9, rel=2e-3)  # ISSUE: 1.245 B
    assert 2 * k.parameter_count(mm) == pytest.approx(9.8e9, rel=1e-2)  # ISSUE: 9.8 GB in bf16
    # active parameters of a token: head + attention + 8 experts; 6 FLOPs each, plus the scores
    active = 2048 * 151936 + 2048 * (4096 + 2 * 512) + 4096 * 2048 + 8 * 3 * 2048 * 768
    scores = 3 * 2 * 2 * 4096 * (4096 / 2)
    assert k.train_flops_per_token(sdar, 4096) == pytest.approx(6 * active + scores)


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
