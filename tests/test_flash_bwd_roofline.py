"""``kernel.flash_bwd_roofline`` (PERF.md section 3, kernels): the reader on
hand-made kernel records with the train cells' shapes, as the parent's
backward call has them (six 4-d operands) and as the call that reads its
layer of the stacks has them (a prefetched scalar, then 5-d stacks), and
the metric through the manifest of both cells. Nothing runs a model
here; ``tests/test_tpu_compile_train.py`` holds the compiled steps' own
calls to the same classifier."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, compute_metrics  # noqa: E402
from benchmark.readers import flash_bwd_roofline, flash_roofline  # noqa: E402
from benchmark.trace import opsbytes  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
LARGE = {"n_head": 20, "n_embd": 1280}
XL = {"n_head": 25, "n_embd": 1600}


def _backward(b, rows, layers=None, calls=36, us_a_call=715.0):
    """The backward call's record as ``trace/reduce.py`` hands it on."""
    row, lanes = (b, rows, 1024, 128), (b, 2 * rows, 4, 256)
    stack = lambda dims: dims if layers is None else (layers,) + dims
    operands = [("bf16", stack(row))] * 3 + [
        ("bf16", row), ("f32", stack(lanes)), ("f32", lanes)]
    if layers is not None:
        operands.insert(0, ("s32", (1,)))
    return {"outputs": [("bf16", row)] * 3, "operands": operands,
            "short": "bwd", "calls": calls,
            "seconds": calls * us_a_call * 1e-6}


def _forward(b, rows):
    row = (b, rows, 1024, 128)
    return {"outputs": [("bf16", row), ("f32", (b, 2 * rows, 1024, 1))],
            "operands": [("bf16", row)] * 3, "short": "fwd", "calls": 36,
            "seconds": 36 * 423e-6}


def _ctx(kernels, config):
    return {"trace": {"kernels": kernels}, "peaks": PEAKS, "config": config}


@pytest.mark.parametrize("layers", [None, 36])
def test_the_call_is_known_whatever_its_operands_rank(layers):
    k = _backward(8, 10, layers)
    assert flash_bwd_roofline.backward_call(k) == (8, 10, 1024, 1024, 128)
    assert flash_bwd_roofline.backward_call(_forward(8, 10)) is None
    # 20 heads of 64 lanes, causal: 10 x b x h x s x s x d / 2 operations,
    # compute-bound, 272.5 us a call at the published peak
    flops, _ = opsbytes.flash_backward(8, 20, 1024, 1024, 64, True)
    got = flash_bwd_roofline.read(_ctx([k, _forward(8, 10)], LARGE))
    assert got == pytest.approx(100 * flops / 197e12 / 715e-6)
    assert 38.0 < got < 38.2


def test_the_parents_call_reads_what_the_accepted_reader_reads():
    """Where ``kernel.flash_roofline`` can see the call (the parent's, on
    a head count that fills its rows) both count the same work."""
    ctx = _ctx([_backward(8, 10)], LARGE)
    assert flash_bwd_roofline.read(ctx) == pytest.approx(
        flash_roofline.read(ctx, causal=True))
    stacked = _ctx([_backward(8, 10, layers=36)], LARGE)
    assert flash_roofline.read(stacked, causal=True) is None
    assert flash_bwd_roofline.read(stacked) == flash_bwd_roofline.read(ctx)


def test_a_zero_head_is_no_work():
    """``gpt2-xl``: 25 heads in 13 rows of two, 6 sequences a device."""
    k = _backward(6, 13, layers=48, calls=4 * 48, us_a_call=700.0)
    flops, _ = opsbytes.flash_backward(6, 25, 1024, 1024, 64, True)
    assert flash_bwd_roofline.read(_ctx([k], XL)) == pytest.approx(
        100 * flops / 197e12 / 700e-6)


def test_nothing_to_read_is_none_and_no_error():
    k = _backward(8, 10, layers=36)
    assert flash_bwd_roofline.read(_ctx([], LARGE)) is None
    assert flash_bwd_roofline.read(_ctx([_forward(8, 10)], LARGE)) is None
    assert flash_bwd_roofline.read({"trace": None, "peaks": PEAKS}) is None
    assert flash_bwd_roofline.read(_ctx([k], {"num_heads": 20})) is None
    # a width the configuration's heads do not divide is another kernel's
    assert flash_bwd_roofline.read(_ctx([k], {"n_head": 12,
                                              "n_embd": 1152})) is None


@pytest.mark.parametrize("cell,config,b,rows", [
    ("gpt2-large.pretrain_1k", LARGE, 8, 10),
    ("gpt2-xl.pretrain_1k_fsdp4", XL, 6, 13)])
def test_both_train_cells_report_it_in_a_traced_run(cell, config, b, rows):
    manifest = Manifest(ROOT)
    specs = manifest.cell(cell)["metrics"]["per_layer"]
    assert "kernel.flash_bwd_roofline" in [s["name"] for s in specs]
    cfg = manifest.cell(cell)["config"]
    assert (cfg["n_head"], cfg["n_embd"]) == (config["n_head"],
                                              config["n_embd"])
    ctx = _ctx([_backward(b, rows, layers=cfg["n_layer"])], cfg)
    got = compute_metrics(manifest, [s for s in specs if s["reader"] in (
        "flash_roofline", "flash_bwd_roofline")], ctx)
    assert got["kernel.flash_bwd_roofline"]["unit"] == "%"
    assert 30 < got["kernel.flash_bwd_roofline"]["value"] < 45
    assert "kernel.flash_roofline" not in got
    serving = manifest.cell("smollm2-1.7b.chat_steady")["metrics"]
    assert "kernel.flash_bwd_roofline" not in [
        s["name"] for s in serving["per_layer"]]
