"""``kernel.flash_fwd_roofline`` (PERF.md section 3, kernels): the reader on
hand-made kernel records with the train cells' shapes, as the parent's
forward call has them (three 4-d operands, lse ``[b, heads, sq, 1]``) and as
the call that writes its layer of the stacks has them (a prefetched scalar,
the two stacks aliased to its results, lse as rows of lanes), and the metric
through the manifest of both cells. Nothing runs a model here;
``tests/test_tpu_compile_train.py`` holds the compiled steps' own calls to
the same classifier."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest, compute_metrics  # noqa: E402
from benchmark.readers import flash_fwd_roofline, flash_roofline  # noqa: E402
from benchmark.trace import opsbytes  # noqa: E402
from test_flash_bwd_roofline import LARGE, XL, _backward, _ctx  # noqa: E402


def _forward(b, rows, layers=None, calls=36, us_a_call=423.0, heads_a_row=2):
    """The forward call's record as ``trace/reduce.py`` hands it on."""
    row = (b, rows, 1024, 128 // 2 * heads_a_row)
    heads = rows * heads_a_row
    operands = [("bf16", row)] * 3
    outputs = [("bf16", row), ("f32", (b, heads, 1024, 1))]
    if layers is not None:
        outputs = [("bf16", (layers,) + row),
                   ("f32", (layers, b, heads, 4, 256))]
        operands = [("s32", (1,))] + operands + outputs
    return {"outputs": outputs, "operands": operands, "short": "fwd",
            "calls": calls, "seconds": calls * us_a_call * 1e-6}


@pytest.mark.parametrize("layers", [None, 36])
def test_the_call_is_known_whatever_its_layout(layers):
    k = _forward(8, 10, layers)
    assert flash_fwd_roofline.forward_call(k) == (8, 10, 1024, 1024, 128)
    for stacked in (None, 36):
        assert flash_fwd_roofline.forward_call(
            _backward(8, 10, stacked)) is None
    # 20 heads of 64 lanes, causal: 4 x b x h x s x s x d / 2 operations,
    # compute-bound, 109.0 us a call at the published peak
    flops, _ = opsbytes.flash_forward(8, 20, 1024, 1024, 64, True)
    got = flash_fwd_roofline.read(_ctx([k, _backward(8, 10, layers)], LARGE))
    assert got == pytest.approx(100 * flops / 197e12 / 423e-6)
    assert 25.7 < got < 25.9


def test_an_unpacked_call_reads_what_the_accepted_reader_reads():
    """Where ``kernel.flash_roofline`` can see the call (heads whole,
    ``[b, h, s, 64]``, lse with the operands' ``h``) both count the same
    work; the packed call and the stacked one are out of its sight."""
    ctx = _ctx([_forward(8, 20, heads_a_row=1)], LARGE)
    assert flash_fwd_roofline.read(ctx) == pytest.approx(
        flash_roofline.read(ctx, causal=True))
    for layers in (None, 36):
        packed = _ctx([_forward(8, 10, layers)], LARGE)
        assert flash_roofline.read(packed, causal=True) is None
        assert flash_fwd_roofline.read(packed) == pytest.approx(
            flash_fwd_roofline.read(ctx))


def test_a_zero_head_is_no_work_in_the_forward_either():
    """``gpt2-xl``: 25 heads in 13 rows of two, 6 sequences a device."""
    k = _forward(6, 13, layers=48, calls=4 * 48, us_a_call=385.5)
    flops, _ = opsbytes.flash_forward(6, 25, 1024, 1024, 64, True)
    got = flash_fwd_roofline.read(_ctx([k], XL))
    assert got == pytest.approx(100 * flops / 197e12 / 385.5e-6)
    assert 26.4 < got < 26.6


def test_nothing_of_the_forward_to_read_is_none_and_no_error():
    k = _forward(8, 10, layers=36)
    assert flash_fwd_roofline.read(_ctx([], LARGE)) is None
    assert flash_fwd_roofline.read(_ctx([_backward(8, 10, 36)],
                                        LARGE)) is None
    assert flash_fwd_roofline.read({"trace": None, "peaks": {}}) is None
    assert flash_fwd_roofline.read(_ctx([k], {"num_heads": 20})) is None
    # a width the configuration's heads do not divide is another kernel's
    assert flash_fwd_roofline.read(_ctx([k], {"n_head": 12,
                                              "n_embd": 1152})) is None
    # two results of which the second is no lse of the first's rows
    other = dict(k, outputs=[k["outputs"][0], ("f32", (36, 8, 20, 4, 128))])
    assert flash_fwd_roofline.forward_call(other) is None


@pytest.mark.parametrize("layers", [None, "cell"])
@pytest.mark.parametrize("cell,config,b,rows", [
    ("gpt2-large.pretrain_1k", LARGE, 8, 10),
    ("gpt2-xl.pretrain_1k_fsdp4", XL, 6, 13)])
def test_both_train_cells_report_the_forward_in_a_traced_run(
        cell, config, b, rows, layers):
    """... from the parent's call (the driver lays this reader over the
    parent's checkout too) and from the one that writes the stacks."""
    manifest = Manifest(ROOT)
    specs = manifest.cell(cell)["metrics"]["per_layer"]
    assert "kernel.flash_fwd_roofline" in [s["name"] for s in specs]
    cfg = manifest.cell(cell)["config"]
    k = _forward(b, rows, layers=layers and cfg["n_layer"])
    got = compute_metrics(manifest, [s for s in specs if s["reader"] in (
        "flash_roofline", "flash_fwd_roofline", "flash_bwd_roofline")],
        _ctx([k], cfg))
    assert got["kernel.flash_fwd_roofline"]["unit"] == "%"
    assert 20 < got["kernel.flash_fwd_roofline"]["value"] < 30
    assert list(got) == ["kernel.flash_fwd_roofline"]
    serving = manifest.cell("smollm2-1.7b.chat_steady")["metrics"]
    assert "kernel.flash_fwd_roofline" not in [
        s["name"] for s in serving["per_layer"]]
