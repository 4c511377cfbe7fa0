"""Metric arithmetic: percentiles, rates, MFU, and that a reader with
nothing to read returns nothing (the harness then leaves the metric out)."""

import pytest

from benchmark.readers import (counter, exposed_collective, kernel_share,
                               mfu, module_ms, rate, series_stat,
                               slot_occupancy)
from benchmark.trace import opsbytes


def test_percentiles_are_nearest_rank():
    ctx = {"series": {"x": [float(i) for i in range(1, 101)]}}
    assert series_stat.read(ctx, "x", 90) == 90.0
    assert series_stat.read(ctx, "x", 50) == 50.0
    assert series_stat.read(ctx, "x", 100) == 100.0
    assert series_stat.read(ctx, "x", "mean") == 50.5
    assert series_stat.read({"series": {"x": [3.0, 1.0, 2.0]}}, "x", 90) == 3.0
    assert series_stat.read({"series": {"x": [7.0]}}, "x", 90) == 7.0
    assert series_stat.read(ctx, "absent", 90) is None
    assert series_stat.read({"series": {"x": []}}, "x", 50) is None


def test_rate_and_counter():
    ctx = {"counters": {"out_tokens": 900, "window_s": 45.0, "setup_s": 3.5}}
    assert rate.read(ctx, "out_tokens") == 20.0
    assert rate.read(ctx, "train_tokens") is None
    assert counter.read(ctx, "setup_s") == 3.5


def test_gpt2_flops_and_mfu():
    large = {"n_layer": 36, "n_embd": 1280, "n_head": 20,
             "n_positions": 1024, "vocab_size": 50304, "n_inner": None}
    n = opsbytes.gpt2_params(large)
    assert 7.7e8 < n < 7.8e8  # "774M"
    per_token = opsbytes.gpt2_train_flops_per_token(large, 1024)
    assert per_token == 6.0 * n + 12.0 * 36 * 1280 * 1024
    ctx = {"counters": {"tokens_per_step": 8192, "seq": 1024},
           "series": {"cycle_ms": [8192 / 18.0] * 5 + [9000.0]},
           "config": large, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12}}
    got = mfu.read(ctx, "gpt2_train_flops_per_token")
    assert got == pytest.approx(100 * 18000 * per_token / 197e12)
    assert 45 < got < 50


def test_trace_readers_need_a_trace():
    for reader, args in ((module_ms, {"pattern": "x"}), (kernel_share, {}),
                         (exposed_collective, {}),
                         (slot_occupancy, {"pattern": "x"})):
        assert reader.read({"trace": None, "counters": {}}, **args) is None


def test_slot_occupancy_and_module_ms():
    trace = {"window_s": 5.0,
             "modules": {"jit_block_fn": [0.05, 0.07],
                         "jit_decode_only_fn": [0.04, 0.04, 0.04],
                         "jit_other": [9.0]}}
    pat = "^jit_(block_fn|decode_only_fn)$"
    assert module_ms.read({"trace": trace}, pat) == pytest.approx(40.0)
    # 5 steps x 16 slots in 5 s = 16 slot-steps/s; 32 decode tokens in the
    # 4 s the counters span = 8/s
    ctx = {"trace": trace, "counters": {
        "trace_tokens": 34, "trace_requests": 2, "trace_counts_s": 4.0,
        "decode_block": 1, "num_slots": 16}}
    assert slot_occupancy.read(ctx, pat) == pytest.approx(50.0)
