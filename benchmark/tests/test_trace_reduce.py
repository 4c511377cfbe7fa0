"""The trace reduction, on two traces recorded on the chip by
``benchmark/tools/trace_probe.py`` (one v5e chip; one four-chip host) and
on a hand-made trace whose answers are known."""

import os

import pytest

from benchmark.trace import opsbytes, reduce as tr

from conftest import FIXTURES


@pytest.fixture(scope="module")
def one_chip():
    return tr.reduce(tr.load_xplane(os.path.join(FIXTURES,
                                                 "1chip.xplane.pb")))


@pytest.fixture(scope="module")
def four_chips():
    return tr.reduce(tr.load_xplane(os.path.join(FIXTURES,
                                                 "4chip.xplane.pb")))


def test_busy_and_idle_of_the_recorded_trace(one_chip):
    # three executions of a ~38 us program inside a ~9 ms traced window
    assert one_chip["devices"] == 1
    assert one_chip["modules"]["jit_loss"] == pytest.approx(
        [38.1e-6, 37.9e-6, 37.8e-6], rel=0.01)
    assert 100e-6 < one_chip["busy_s"] < 3 * 38.2e-6
    assert 5e-3 < one_chip["window_s"] < 20e-3
    idle = 1 - one_chip["busy_s"] / one_chip["window_s"]
    assert 0.98 < idle < 0.995
    # the three long gaps are the host inside the benchmark's annotation
    assert [g[0] for g in one_chip["idle_gaps"][:3]] == \
        ["python: bench.attn_step"] * 3


def test_one_kernels_time_and_roofline(one_chip):
    kinds = {opsbytes.classify_flash(k)[0]: k for k in one_chip["kernels"]}
    assert set(kinds) == {"fwd", "bwd"}
    fwd = kinds["fwd"]
    assert fwd["calls"] == 3
    assert fwd["seconds"] / 3 == pytest.approx(7.32e-6, rel=0.02)
    assert opsbytes.classify_flash(fwd) == ("fwd", 2, 4, 512, 512, 64)
    flops, nbytes = opsbytes.flash_forward(2, 4, 512, 512, 64, True)
    assert flops == 4 * 2 * 4 * 512 * 512 * 64 / 2
    assert nbytes == 2 * 2 * 4 * 64 * 2048 + 4 * 2 * 4 * 512
    from benchmark.readers import flash_roofline

    ctx = {"trace": one_chip, "peaks": {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9}}
    share = flash_roofline.read(ctx, causal=True)
    assert 5 < share < 100  # a tiny shape is far from the roofline


def test_exposed_collective_of_the_recorded_trace(four_chips):
    # the probe's all-gather feeds the next operation: nothing hides it
    assert four_chips["devices"] == 4
    assert four_chips["collective_s"] == pytest.approx(853e-6, rel=0.02)
    assert four_chips["exposed_collective_s"] == pytest.approx(
        four_chips["collective_s"])
    assert four_chips["device_ops"][0][0].startswith("all-gather.5 "
                                                     "all-gather")
    assert len(four_chips["busy_s_per_device"]) == 4


def _space(ops, async_ops=(), host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": list(ops)},
            {"name": "Async XLA Ops", "events": list(async_ops)},
            {"name": "XLA Modules", "events": [("jit_step(1)", 0.0, 100.0)]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python/1",
                                         "events": list(host)}]}]}


def test_exposed_collective_arithmetic():
    ag = "%ag = bf16[8]{0} all-gather-start(bf16[2]{0} %p), channel_id=1"
    done = "%agd = bf16[8]{0} all-gather-done(bf16[8]{0} %ag)"
    fusion = "%f = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop"
    # async all-gather spans 10..60; compute covers 20..50; the done
    # marker waits 50..60. Exposed: 10..20 and 50..60 = 20 ns of 50.
    red = tr.reduce(_space(
        ops=[(ag, 10.0, 1.0), (fusion, 20.0, 30.0), (done, 50.0, 10.0)],
        async_ops=[(ag, 10.0, 50.0)],
        host=[("bench.wait", 0.0, 10.0), ("other", 60.0, 40.0)]))
    assert red["collective_s"] == pytest.approx(50e-9)
    assert red["exposed_collective_s"] == pytest.approx(20e-9)
    assert red["busy_s"] == pytest.approx(41e-9)  # 10..11, 20..50, 50..60
    assert red["window_s"] == pytest.approx(100e-9)
    gaps = dict(red["idle_gaps"])
    assert gaps["python: other"] == pytest.approx(40e-9)
    assert gaps["python: bench.wait"] == pytest.approx(10e-9)


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.subtract([(0, 4)], [(0, 4)]) == []


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_parse_op():
    op = tr.parse_op(
        "%all-gather.5 = bf16[8192,2048]{1,0:T(8,128)(2,1)S(1)} "
        "all-gather(bf16[2048,2048]{1,0:T(8,128)(2,1)} %param), "
        "channel_id=1")
    assert op["short"] == "all-gather.5" and op["opcode"] == "all-gather"
    assert op["outputs"] == [("bf16", (8192, 2048))]
    assert op["operands"] == [("bf16", (2048, 2048))]
    assert tr.is_collective(op["opcode"]) and not tr.is_compute(op)
