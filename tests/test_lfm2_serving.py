"""The lfm2 family through the serving engine, at a tiny size on the CPU
(one lead layer and one whole period, 8 experts top-2, float32): the
short convolution's state a slot beside the KV pages, the sigmoid-routed
experts, and the float32 reference both are held to
(``benchmark/reference/lfm2_moe.py``). Logits are compared, not sampled
tokens; both sides are float32 here, so only the order of summation
differs and every tolerance is a few float32 ulps of a logit of size ~1.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402
from benchmark.traffic import closed_loop  # noqa: E402
from ray_tpu.llm.engine import SlotEngine  # noqa: E402
from ray_tpu.models import lfm2, llama, serving  # noqa: E402

CFG = lfm2.CONFIGS["lfm2-tiny"]
# the tiny preset as the benchmark's configuration file would spell it
REF_CFG = {"num_attention_heads": CFG.num_heads,
           "num_key_value_heads": CFG.num_kv_heads,
           "rope_parameters": {"rope_theta": CFG.rope_theta},
           "norm_eps": CFG.norm_eps, "conv_L_cache": CFG.conv_L_cache,
           "num_experts_per_tok": CFG.num_experts_per_tok,
           "use_expert_bias": True, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0,
           "layer_types": list(CFG.layer_types),
           "num_dense_layers": CFG.num_dense_layers}
PAGE, CHUNK, SLOTS = 8, 16, 4
# float32 on both sides: summation order alone, on logits of size ~1
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(jax.random.PRNGKey(0), CFG)[0]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, size=n).tolist()


def _engine(params, **kw):
    kw.setdefault("num_slots", SLOTS)
    return SlotEngine(params, CFG, chunk=CHUNK, page_size=PAGE, **kw)


def _run(engine, prompt, max_new=8, **kw):
    h = engine.submit(prompt, max_new=max_new, **kw)
    while not h._done.is_set():
        assert engine.step()
    return h.result(timeout=0).tokens


def _gap(params, prompt, tokens):
    return ref.check_generated(params, REF_CFG,
                               [{"prompt": prompt, "tokens": tokens}])


def test_chunked_prefill_then_decode_equals_the_reference_logits(params):
    """The family's step driven as the engine drives it — a 39-token
    prompt in chunks of 16 (every chunk boundary lies inside a window of
    the 3-tap convolution), then decode rows — gives at every position the
    logits of the reference's one full forward pass."""
    model = serving.model_for(CFG)
    prompt = _prompt(1, 39)
    follow = _prompt(2, 6)
    want = np.asarray(ref.logits(params, REF_CFG, prompt + follow))
    cache = model.slot_state.attach(CFG, model.init_cache(CFG, 33, PAGE),
                                    SLOTS)
    tables = np.zeros((SLOTS, CFG.max_seq // PAGE), np.int32)
    slot = 2
    tables[slot, :8] = np.arange(1, 9)
    step = jax.jit(lambda cache, toks, pos, chunk: model.step(
        params, cache, jnp.asarray(tables), toks, pos, chunk, CFG, PAGE))
    parked = jnp.full((SLOTS,), CFG.max_seq, jnp.int32)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    for p0 in range(0, len(prompt), CHUNK):
        piece = prompt[p0:p0 + CHUNK]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(piece)] = piece
        _, pre, cache, _ = step(cache, zeros, parked, (
            jnp.asarray(buf), jnp.int32(slot), jnp.int32(p0),
            jnp.int32(len(piece))))
        # the chunk's last valid token's logits
        got = np.asarray(pre)
        assert np.abs(got - want[p0 + len(piece) - 1]).max() < TOL
    for i, tok in enumerate(follow):
        pos = parked.at[slot].set(len(prompt) + i)
        logits, _, cache, counts = step(cache, zeros.at[slot].set(tok), pos,
                                        None)
        assert np.abs(np.asarray(logits[slot])
                      - want[len(prompt) + i]).max() < TOL
        # one valid row, top-2, three expert layers
        assert np.asarray(counts).tolist()[1] == 2 * 4


def test_engine_tokens_lie_on_the_references_argmax(params):
    """Through ``SlotEngine`` itself: every generated token's reference
    logit is the position's largest, to summation order."""
    prompt = _prompt(3, 45)
    engine = _engine(params)
    tokens = _run(engine, prompt, max_new=12)
    res = _gap(params, prompt, tokens)
    assert res["n"] == 12 and res["finite"] and res["max_gap"] < TOL
    # a wrong token is seen
    wrong = [(t + 1) % CFG.vocab_size for t in tokens]
    assert _gap(params, prompt, wrong)["max_gap"] > 0.01
    # the step's own counts arrived with its tokens
    assert engine.expert_rows > 0 and engine.experts_hit > 0
    assert engine.expert_rows_max <= engine.expert_rows


def test_reused_slot_and_parked_rows_start_from_zero_state(params):
    """A slot that served one request, and rows that sat parked while
    another slot decoded, give the next request the tokens and the conv
    state a fresh engine gives it."""
    first, second = _prompt(4, 30), _prompt(5, 21)
    used = _engine(params, num_slots=2)
    _run(used, first, max_new=9)          # slot 0 used, slot 1 parked
    got = _run(used, second, max_new=7)   # slot 0 again
    fresh = _engine(params, num_slots=2)
    want = _run(fresh, second, max_new=7)
    assert got == want
    assert _gap(params, second, got)["max_gap"] < TOL
    # the first request left state behind in its slot (what the block in
    # flight at its end wrote there too); the parked slot's was never
    # written, in either engine
    for a, b in zip(used._cache["conv"], fresh._cache["conv"]):
        assert np.asarray(a[0]).any()
        assert not np.asarray(a[1]).any() and not np.asarray(b[1]).any()


def test_repeated_prompt_with_prefix_cache_on_equals_the_cold_run(params):
    """``prefix_cache`` defaults to True; for a family with slot state the
    engine keeps no index and takes no hit, so the second run of a prompt
    prefills from position 0 again and says what the first said."""
    prompt = _prompt(6, 50)
    engine = _engine(params, prefix_cache=True)
    cold = _run(engine, prompt)
    again = _run(engine, prompt)
    assert again == cold
    assert engine.prefix_hits == 0 and engine.prefix_cache_len() == 0
    assert _gap(params, prompt, again)["max_gap"] < TOL


def test_session_travels_as_transcript_and_pages_are_refused(params):
    prompt = _prompt(7, 33)
    src = _engine(params)
    tokens = _run(src, prompt, max_new=6, session_id="s")
    snap = src.export_session("s")
    assert snap["pages_kv"] is None and snap["covered_tokens"] == 0
    assert snap["transcript"].tolist() == prompt + tokens
    dst = _engine(params)
    info = dst.import_session(snap)
    assert info["pages_imported"] == 0 and dst.sessions() == ["s"]
    # the next turn re-prefills there and agrees with the reference
    turn = snap["transcript"].tolist() + _prompt(8, 5)
    out = _run(dst, turn, max_new=5, session_id="s")
    assert _gap(params, turn, out)["max_gap"] < TOL
    # pages without the state at their boundary: the typed refusal
    frames = np.zeros((1, 2, 1, PAGE, CFG.num_kv_heads * CFG.head_dim),
                      np.float32)
    with pytest.raises(serving.SlotStateError, match="state a slot"):
        dst.import_session(dict(snap, pages_kv=frames, covered_tokens=PAGE))


def _dense_experts(u, experts, weights, p):
    """Every pick computed on its own, in float64 on the host."""
    f = CFG.d_expert
    u, w13, w2 = (np.asarray(a, np.float64)
                  for a in (u, p["w_gate_up"], p["w_down"]))
    out = np.zeros_like(u)
    for n in range(u.shape[0]):
        for e, w in zip(np.asarray(experts[n]), np.asarray(weights[n])):
            h = u[n] @ w13[e]
            out[n] += w * ((h[:f] / (1 + np.exp(-h[:f])) * h[f:]) @ w2[e])
    return out


def test_every_row_on_one_expert_loses_no_token(params):
    """No capacity: all 24 rows routed to the same two experts are all
    multiplied, and rows that are not valid cost and change nothing."""
    p = params["layers"][1]
    n = 24
    u = jax.random.normal(jax.random.PRNGKey(1), (n, CFG.d_model))
    experts = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (n, 1))
    weights = jax.random.uniform(jax.random.PRNGKey(2), (n, 2))
    valid = jnp.ones((n,), bool)
    out, counts = lfm2.experts_ffn(u, experts, weights, valid, p, CFG)
    want = _dense_experts(u, experts, weights, p)
    assert np.abs(np.asarray(out) - want).max() < 1e-5
    assert np.asarray(counts).tolist() == [2, 2 * n, n]
    valid = valid.at[3:9].set(False)
    out, counts = lfm2.experts_ffn(u, experts, weights, valid, p, CFG)
    assert not np.asarray(out[3:9]).any()
    assert np.abs(np.asarray(out[9:]) - want[9:]).max() < 1e-5
    assert np.asarray(counts).tolist() == [2, 2 * (n - 6), n - 6]


@pytest.mark.parametrize("m", [128, 200])
def test_grouped_matmul_kernel_matches_ragged_dot(m):
    """The TPU's kernel, interpreted, against ``lax.ragged_dot``: uneven
    groups, empty groups, rows behind the last group (unspecified, so not
    compared) and a row count off the kernel's row tile."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    g, k, n = 6, 128, 256
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (g, k, n)) * 0.1
    sizes = jnp.asarray([40, 0, 1, 70, 0, 9], jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    got = grouped_matmul(lhs, rhs, sizes, interpret=True)
    live = int(sizes.sum())
    assert got.shape == (m, n)
    assert np.abs(np.asarray(got[:live] - want[:live])).max() < 1e-4


def test_expert_bias_changes_the_selection_and_not_the_weights(params):
    p = dict(params["layers"][1])
    u = jax.random.normal(jax.random.PRNGKey(3), (16, CFG.d_model))
    scores = np.asarray(jax.nn.sigmoid(u @ p["router"]), np.float64)
    p["expert_bias"] = jnp.zeros((CFG.num_experts,)).at[6].set(10.0)
    experts, weights = lfm2.route(u, p, CFG)
    experts, weights = np.asarray(experts), np.asarray(weights)
    assert (experts == 6).any(axis=1).all()        # the bias picks it ...
    unbiased, _ = lfm2.route(
        u, dict(p, expert_bias=jnp.zeros((CFG.num_experts,))), CFG)
    assert not (np.asarray(unbiased) == 6).any(axis=1).all()
    picked = np.take_along_axis(scores, experts, axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 1e-6)
    # ... and the weights are the scores', the bias nowhere in them
    assert np.abs(weights - want).max() < 1e-6
    # the reference routes the same way
    dense = np.asarray(ref.routing(u, p, 2, True, True, 1.0))
    assert np.abs(np.take_along_axis(dense, experts, axis=1)
                  - weights).max() < 1e-6
    assert ((dense > 0).sum(axis=1) == 2).all()


def test_two_seeds_give_the_cell_the_same_sizes_in_the_same_order():
    manifest = Manifest(ROOT)
    cell = manifest.cell("lfm2-24b-a2b.decode_heavy_closed")
    assert cell["chips"] == 1
    assert cell["config"]["driver"] == "serve_lfm2"
    assert cell["config"]["reference"] == "lfm2_moe"
    traffic = dict(cell["traffic"], requests_per_client=2)
    plans = [closed_loop.plan(traffic, seed, 51.0, 65536,
                              deployment=cell["config"]["deployment"])
             for seed in (7, 3000003107)]
    sizes = [[(len(r["prompt"]), r["max_tokens"]) for r in p["requests"]]
             for p in plans]
    assert sizes[0] == sizes[1] and plans[0]["clients"] == 128
    assert plans[0]["requests"][0]["prompt"] != \
        plans[1]["requests"][0]["prompt"]
    assert all(64 <= a <= 256 and 256 <= b <= 768 for a, b in sizes[0])


def test_the_manifest_resolves_the_new_cell_and_its_configuration():
    from benchmark.drivers.serve_lfm2_replica import lfm2_config

    manifest = Manifest(ROOT)
    cell = manifest.cell("lfm2-24b-a2b.decode_heavy_closed")
    e2e = [m["name"] for m in cell["metrics"]["end_to_end"]]
    assert sorted(e2e) == ["out_tokens_per_s", "setup_s"]
    per_layer = {m["name"] for m in cell["metrics"]["per_layer"]}
    assert {
            # PR 56: the engine loop's own account, one file a metric for
            # the three cells (tests/test_loop_account.py)
            "engine.hole_ms", "engine.caller_cpu_share",
            "engine.submit_p90_ms",
            "step.decode_ms.lfm2", "step.moe_share", "step.conv_share",
            "kernel.moe_roofline", "moe.experts_hit_share",
            "moe.load_max_share", "engine.slot_occupancy",
            # PR 37: the engine's own spans (tests/test_host_metrics.py
            # holds each to its file and reader)
            "engine.dispatch_p50_ms.lfm2", "engine.launch_p50_ms.lfm2",
            "engine.dispatch_off_cpu_share.lfm2",
            "engine.deliver_off_cpu_share.lfm2",
            "engine.idle_dispatch_share.lfm2",
            "engine.idle_unnamed_share.lfm2", "engine.gc_pause_ms.lfm2",
            "engine.compiles_in_trace.lfm2",
            "engine.active_slot_share.lfm2",
            "engine.prefill_wait_share.lfm2"} == per_layer
    cfg = lfm2_config(cell["config"])
    published = lfm2.CONFIGS["lfm2-24b-a2b"]
    # every width as published; the cut is depth and positions alone
    for key in ("vocab_size", "d_model", "num_heads", "num_kv_heads",
                "d_mlp", "d_expert", "num_experts", "num_experts_per_tok",
                "conv_L_cache", "norm_eps", "rope_theta"):
        assert getattr(cfg, key) == getattr(published, key), key
    assert cfg.layer_types == ("conv",) + lfm2.PERIOD * 2
    assert cfg.num_dense_layers == 1 and cfg.max_seq == 2048
    assert cell["config"]["deployment"]["num_slots"] in (64, 48, 32)


def test_the_llama_familys_cache_and_step_are_what_they_were():
    """The contract grew for families that need it; the one that does not
    declares no slot state, keeps a one-leaf cache and a three-result
    step, and the engine builds it no reset program."""
    model = serving.model_for(llama.CONFIGS["llama-tiny"])
    assert model.slot_state is None and model.step_counters == ()
    cfg = llama.CONFIGS["llama-tiny"]
    cache = jax.eval_shape(lambda: model.init_cache(cfg, 9, 8))
    assert list(cache) == ["kv"] and cache["kv"].shape == (
        cfg.num_layers, 2, 9, 8, cfg.num_kv_heads * cfg.head_dim)
    params = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), cfg)[0])
    out = jax.eval_shape(
        lambda p, c: model.step(
            p, c, jnp.zeros((2, cfg.max_seq // 8), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), None,
            cfg, 8), params, cache)
    assert len(out) == 3 and out[1] is None
    engine = SlotEngine(llama.init_params(jax.random.PRNGKey(0), cfg)[0],
                        cfg, num_slots=2, page_size=8, chunk=16)
    assert engine._reset_slots is None and engine._radix is not None
    assert list(engine._cache) == ["kv"]


def test_the_cells_driver_end_to_end_at_a_tiny_size(tmp_path):
    """``benchmark/drivers/serve_lfm2.py`` as ``benchmark/run.py`` calls
    it, on the CPU: a real replica through ``serve.run`` and HTTP, a tiny
    configuration of this family under a tiny closed loop, the counters,
    the repeated request and the float32 reference deciding ``correct``."""
    import json
    import shutil
    import time

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "tools"))
    config = {
        "name": "tiny-lfm2", "driver": "serve_lfm2", "reference": "lfm2_moe",
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 64,
        "intermediate_size": 160, "layer_types": list(CFG.layer_types),
        "max_position_embeddings": 128, "moe_intermediate_size": 48,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
        "num_hidden_layers": 5, "num_key_value_heads": 2,
        "rope_parameters": {"rope_theta": 1000000.0},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 512, "torch_dtype": "float32",
        "deployment": {"num_slots": 4, "page_size": 8, "num_pages": None}}
    traffic = {"generator": "closed_loop", "clients_per_slot": 2,
               "requests_per_client": 40, "shape_seed": 5,
               "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
               "output_len": {"dist": "uniform", "min": 8, "max": 24},
               "stream": True, "start_stagger_s": 0.05, "ramp_s": 1.0,
               "grace_s": 30}
    with open(tmp_path / "benchmark/configs/tiny-lfm2.json", "w") as fh:
        json.dump(config, fh)
    with open(tmp_path / "benchmark/traffic/tiny_decode.json", "w") as fh:
        json.dump(traffic, fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    old = "lfm2-24b-a2b.decode_heavy_closed"
    bench["configs"] = [{"name": "tiny-lfm2", "source": "test",
                         "file": "benchmark/configs/tiny-lfm2.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny.decode", "config": "tiny-lfm2",
                           "traffic": "tiny_decode", "chips": 1,
                           "why": "tiny"}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [dict(m, workloads=["tiny.decode"]) for m in bench[kind]
                       if "workloads" not in m or old in m["workloads"]]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)

    from benchmark.manifest import compute_metrics

    manifest = Manifest(str(tmp_path))
    cell = manifest.cell("tiny.decode")
    driver = manifest.load_module("drivers", cell["config"]["driver"])
    out = driver.run(manifest, cell, seed=2**31 + 31, seconds=2.0,
                     trace=False, t0=time.time(), log=lambda s: None,
                     rehearsal=True)
    assert out["correct"], out["notes"]
    assert out["failed"] == 0 and out["attempted"] > 0
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=1, seconds=2.0, peaks=manifest.peaks("TPU v5 lite"))
    got = compute_metrics(manifest, cell["metrics"]["end_to_end"], ctx)
    assert got["out_tokens_per_s"]["value"] > 0 and "setup_s" in got
    # untraced, off the chip: no per-layer metric finds anything to read,
    # and none raises for it
    assert compute_metrics(manifest, cell["metrics"]["per_layer"], ctx) == {}


def test_one_program_serves_every_step_and_an_empty_chunk_is_inert(params):
    """The family repeats bit for bit only within one compiled program, so
    the engine never dispatches the pure-decode one for it; the fused
    program's empty chunk (n_valid 0) aimed at a LIVE decode row's slot
    changes neither that row's logits nor its state nor a page."""
    engine = _engine(params)

    def never(*a, **kw):
        raise AssertionError("the decode-only program was dispatched")

    engine._decode_only = never
    prompt = _prompt(9, 20)
    tokens = _run(engine, prompt, max_new=10)
    assert engine.steps_decode_only > 0      # steps without a prompt chunk
    assert _gap(params, prompt, tokens)["max_gap"] < TOL

    model = serving.model_for(CFG)
    cache = model.slot_state.attach(CFG, model.init_cache(CFG, 33, PAGE),
                                    SLOTS)
    cache = jax.tree.map(lambda a: jax.random.normal(
        jax.random.PRNGKey(5), a.shape, a.dtype), cache)
    tables = jnp.asarray(np.arange(SLOTS * 8).reshape(SLOTS, 8) % 32 + 1,
                         jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, CFG.max_seq // PAGE - 8)))
    toks = jnp.asarray(_prompt(10, SLOTS), jnp.int32)
    pos = jnp.asarray([9, 17, CFG.max_seq, 30], jnp.int32)
    empty = (jnp.zeros((CHUNK,), jnp.int32), jnp.int32(1), jnp.int32(0),
             jnp.int32(0))
    alone = model.step(params, cache, tables, toks, pos, None, CFG, PAGE)
    fused = model.step(params, cache, tables, toks, pos, empty, CFG, PAGE)
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(alone[0]),
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves(alone[2]["conv"]),
                    jax.tree.leaves(fused[2]["conv"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # pages: all but the scratch page, which takes every invalid write
    np.testing.assert_allclose(np.asarray(fused[2]["kv"][:, :, 1:]),
                               np.asarray(alone[2]["kv"][:, :, 1:]),
                               atol=1e-6)
    assert np.asarray(fused[3]).tolist() == np.asarray(alone[3]).tolist()


def _route_as_it_was(u, p, cfg):
    """``lfm2.route`` as ``models/lfm2.py`` had it before the expert
    layer moved to ``models/moe.py`` (PR 38's text)."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    pick_by = scores
    if cfg.use_expert_bias:
        pick_by = scores + p["expert_bias"].astype(jnp.float32)
    _, experts = jax.lax.top_k(pick_by, cfg.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def _experts_ffn_as_it_was(u, experts, weights, valid, p, cfg):
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    n, d = u.shape
    e, k, f = cfg.num_experts, cfg.num_experts_per_tok, cfg.d_expert
    flat = jnp.where(valid[:, None], experts, e).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    rows = u[order // k]
    hidden = grouped_matmul(rows, p["w_gate_up"].astype(u.dtype), sizes)
    act = jax.nn.silu(hidden[:, :f]) * hidden[:, f:]
    y = grouped_matmul(act, p["w_down"].astype(u.dtype), sizes)
    y = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None], y, 0)
    back = jnp.argsort(order)
    y = y[back].reshape(n, k, d).astype(jnp.float32)
    w = jnp.where(valid[:, None], weights, 0.0)
    out = jnp.einsum("nkd,nk->nd", y, w).astype(u.dtype)
    counts = jnp.stack([(sizes > 0).sum(), sizes.sum(), sizes.max()])
    return out, counts.astype(jnp.int32)


@pytest.mark.parametrize("with_chunk", [True, False])
def test_the_lowered_step_is_unchanged_by_the_expert_layers_move(
        params, monkeypatch, with_chunk):
    """``route`` and ``experts_ffn`` live in ``models/moe.py`` now, shared
    with the family that holds a SHARE of its experts; with ``held=None``
    the step this family lowers is, instruction for instruction, the one
    it lowered with its own copies."""
    from ray_tpu.models import moe

    assert lfm2.route is moe.route and lfm2.experts_ffn is moe.experts_ffn
    assert lfm2.STEP_COUNTERS == moe.EXPERT_COUNTERS
    model = serving.model_for(CFG)
    cache = jax.eval_shape(lambda: model.slot_state.attach(
        CFG, model.init_cache(CFG, 33, PAGE), SLOTS))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    chunk = (i32(CHUNK), i32(), i32(), i32()) if with_chunk else None

    def lowered():
        return jax.jit(lambda p, c, tables, toks, pos, chunk: lfm2.paged_step(
            p, c, tables, toks, pos, chunk, CFG, PAGE)).lower(
            params, cache, i32(SLOTS, CFG.max_seq // PAGE), i32(SLOTS),
            i32(SLOTS), chunk).as_text()

    now = lowered()
    monkeypatch.setattr(lfm2, "route", _route_as_it_was)
    monkeypatch.setattr(lfm2, "experts_ffn", _experts_ffn_as_it_was)
    assert now == lowered()
    assert "stablehlo.sort" in now  # the expert layer's sort is in it
