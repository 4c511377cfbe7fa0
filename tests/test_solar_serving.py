"""The solar family through the serving engine, at a tiny size on the CPU
(d 64, 4 heads of 16, one period [gqa, kda, kda, kda], 16 experts top-4
of which 4 are held, a shared expert, float32): the delta rule's matrix
state and the convolutions' windows a slot beside the GQA pages, one
chip's share of the routed experts, and the float32 reference all of it
is held to (``benchmark/reference/solar_open2.py``). Logits are compared,
not sampled tokens; both sides are float32 here, so only the order of
summation differs and every tolerance is a few float32 ulps of a logit of
size ~1.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.drivers import serve_solar_replica as replica  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from benchmark.traffic import closed_loop  # noqa: E402
from ray_tpu.llm.engine import SlotEngine  # noqa: E402
from ray_tpu.models import moe, serving, solar  # noqa: E402

CFG = solar.CONFIGS["solar-tiny"]
VOCAB = CFG.vocab_here
PAGE, CHUNK, SLOTS = 8, 16, 4
# float32 on both sides: summation order alone, on logits of size ~1
TOL = 2e-4
STACKS = {"mixed": solar.PERIOD, "kda_only": (solar.KDA,) * 3,
          "gqa_only": (solar.GQA,) * 2}


def _ref_cfg(cfg):
    """A program config as the benchmark's configuration file spells it."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "linear_attn_config": {"num_heads": cfg.kda_heads,
                                   "head_dim": cfg.kda_head_dim},
            "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "norm_topk_prob": True, "routed_scaling_factor": 1.0,
            "experts_held_first": cfg.experts_held[0] if cfg.experts_held
            else 0,
            "num_hidden_layers": cfg.num_layers,
            "gqa_layers": [i for i, op in enumerate(cfg.layer_types)
                           if op == solar.GQA]}


REF_CFG = _ref_cfg(CFG)


@pytest.fixture(scope="module")
def params():
    return solar.init_params(jax.random.PRNGKey(0), CFG)[0]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).tolist()


def _engine(params, cfg=CFG, **kw):
    kw.setdefault("num_slots", SLOTS)
    return SlotEngine(params, cfg, chunk=CHUNK, page_size=PAGE, **kw)


def _run(engine, prompt, max_new=8, **kw):
    h = engine.submit(prompt, max_new=max_new, **kw)
    while not h._done.is_set():
        assert engine.step()
    return h.result(timeout=0).tokens


def _run_threaded(engine, prompt, max_new):
    return engine.submit(prompt, max_new=max_new).result(timeout=120).tokens


def _gap(params, prompt, tokens, cfg=REF_CFG):
    return ref.check_generated(params, cfg,
                               [{"prompt": prompt, "tokens": tokens}])


def _fresh_cache(cfg, slots=SLOTS):
    model = serving.model_for(cfg)
    return model.slot_state.attach(cfg, model.init_cache(cfg, 33, PAGE),
                                   slots)


def _tables(slot):
    tables = np.zeros((SLOTS, CFG.max_seq // PAGE), np.int32)
    tables[slot, :8] = np.arange(1, 9)
    return jnp.asarray(tables)


# -- (a) the step against the reference ------------------------------------------

@pytest.mark.parametrize("stack", list(STACKS))
def test_chunked_prefill_then_decode_equals_the_reference_logits(stack):
    """The family's step driven as the engine drives it — a 39-token
    prompt in chunks of 16 (every boundary inside a window of the 4-tap
    convolutions, the matrix state handed from chunk to chunk), then
    decode rows — gives at every position the logits of the reference's
    one full forward pass: for a KDA-only stack, a GQA-only one and the
    published period."""
    cfg = dataclasses.replace(CFG, layer_types=STACKS[stack])
    params = solar.init_params(jax.random.PRNGKey(1), cfg)[0]
    model = serving.model_for(cfg)
    prompt, follow = _prompt(1, 39), _prompt(2, 6)
    want = np.asarray(ref.logits(params, _ref_cfg(cfg), prompt + follow))
    assert want.shape == (45, VOCAB)
    cache, slot = _fresh_cache(cfg), 2
    tables = _tables(slot)
    step = jax.jit(lambda cache, toks, pos, chunk: model.step(
        params, cache, tables, toks, pos, chunk, cfg, PAGE))
    parked = jnp.full((SLOTS,), cfg.max_seq, jnp.int32)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    for p0 in range(0, len(prompt), CHUNK):
        piece = prompt[p0:p0 + CHUNK]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(piece)] = piece
        _, pre, cache, _ = step(cache, zeros, parked, (
            jnp.asarray(buf), jnp.int32(slot), jnp.int32(p0),
            jnp.int32(len(piece))))
        assert np.abs(np.asarray(pre)
                      - want[p0 + len(piece) - 1]).max() < TOL
    n_kda = cfg.layer_types.count(solar.KDA)
    for i, tok in enumerate(follow):
        pos = parked.at[slot].set(len(prompt) + i)
        logits, _, cache, counts = step(cache, zeros.at[slot].set(tok), pos,
                                        None)
        assert np.abs(np.asarray(logits[slot])
                      - want[len(prompt) + i]).max() < TOL
        counts = dict(zip(solar.STEP_COUNTERS, np.asarray(counts).tolist()))
        # one valid row: its 4 picks in every layer, its state in every
        # KDA layer; of the picks only those of held experts are rows
        assert counts["expert_picks"] == 4 * cfg.num_layers
        assert counts["kda_rows"] == n_kda
        assert counts["expert_rows"] <= counts["expert_picks"]


def test_engine_tokens_lie_on_the_references_argmax(params):
    """Through ``SlotEngine`` itself: every generated token's reference
    logit is the position's largest, to summation order."""
    prompt = _prompt(3, 45)
    engine = _engine(params)
    tokens = _run(engine, prompt, max_new=12)
    res = _gap(params, prompt, tokens)
    assert res["n"] == 12 and res["finite"] and res["max_gap"] < TOL
    wrong = [(t + 1) % VOCAB for t in tokens]
    assert _gap(params, prompt, wrong)["max_gap"] > 0.01
    # the step's own counts arrived with its tokens
    assert engine.expert_picks > 0 and engine.kda_rows > 0
    assert 0 < engine.expert_rows < engine.expert_picks


# -- (b) the shares add up -----------------------------------------------------------

def test_the_shares_of_one_layer_add_up_to_the_uncut_layer(params):
    """Expert parallelism's arithmetic: the routed parts that the four
    chips holding experts 0-3, 4-7, 8-11 and 12-15 compute, plus the
    shared expert counted once, are the layer's FFN as the uncut
    reference computes it (every expert held by one holder)."""
    whole = dataclasses.replace(CFG, experts_held=None)
    p = solar.init_params(jax.random.PRNGKey(3), whole)[0]["layers"][1]
    n = 24
    u = jax.random.normal(jax.random.PRNGKey(4), (n, CFG.d_model))
    valid = jnp.ones((n,), bool)
    experts, weights = moe.route(u, p, whole)
    geo = (CFG.num_experts_per_tok, True, 1.0)
    f32 = {k: a.astype(jnp.float32) for k, a in p.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.ffn(u, f32, geo + (0,)))       # uncut
    total = np.asarray(moe.shared_ffn(u, p))                 # once
    rows = 0
    for first in range(0, CFG.num_experts, 4):
        part = dict(p, w_gate_up=p["w_gate_up"][first:first + 4],
                    w_down=p["w_down"][first:first + 4])
        out, counts = moe.experts_ffn(u, experts, weights, valid, part,
                                      whole, held=(first, 4))
        total = total + np.asarray(out)
        rows += int(counts[1])
        # the reference given this share computes this share
        with jax.default_matmul_precision("highest"):
            share = ref.ffn(u, {k: a.astype(jnp.float32)
                                for k, a in part.items()}, geo + (first,))
        np.testing.assert_allclose(
            np.asarray(out) + np.asarray(moe.shared_ffn(u, p)),
            np.asarray(share), atol=1e-5)
    assert rows == n * CFG.num_experts_per_tok   # every pick held once
    np.testing.assert_allclose(total, want, atol=1e-5)
    # and the one holder of all of them computes the same
    out, _ = moe.experts_ffn(u, experts, weights, valid, p, whole)
    np.testing.assert_allclose(
        np.asarray(out) + np.asarray(moe.shared_ffn(u, p)), want, atol=1e-5)


# -- (c) the state is visible ---------------------------------------------------------

def test_zeroing_the_state_halfway_moves_the_logits(params):
    model = serving.model_for(CFG)
    prompt, slot = _prompt(5, 32), 1
    cache, tables = _fresh_cache(CFG), _tables(1)
    step = jax.jit(lambda cache, toks, pos, chunk: model.step(
        params, cache, tables, toks, pos, chunk, CFG, PAGE))
    parked = jnp.full((SLOTS,), CFG.max_seq, jnp.int32)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    for p0 in range(0, 32, CHUNK):
        _, _, cache, _ = step(cache, zeros, parked, (
            jnp.asarray(prompt[p0:p0 + CHUNK], jnp.int32), jnp.int32(slot),
            jnp.int32(p0), jnp.int32(CHUNK)))
    pos = parked.at[slot].set(32)
    toks = zeros.at[slot].set(7)
    kept = np.asarray(step(cache, toks, pos, None)[0][slot])
    want = np.asarray(ref.logits(params, REF_CFG, prompt + [7]))[-1]
    assert np.abs(kept - want).max() < TOL
    for leaf in ("kda", "conv"):
        wiped = model.slot_state.reset(cache, jnp.asarray([slot]))
        wiped = dict(cache, **{leaf: wiped[leaf]})
        got = np.asarray(step(wiped, toks, pos, None)[0][slot])
        assert np.abs(got - want).max() > 100 * TOL, leaf


def test_parked_rows_and_an_empty_chunk_leave_state_and_pages_alone(params):
    """A step whose every row is parked and whose chunk is empty, aimed at
    a slot with live state, returns the cache bit for bit."""
    model = serving.model_for(CFG)
    cache = jax.tree.map(lambda a: jax.random.normal(
        jax.random.PRNGKey(5), a.shape, a.dtype), _fresh_cache(CFG))
    tables = jnp.asarray(np.arange(SLOTS * 8).reshape(SLOTS, 8) % 32 + 1,
                         jnp.int32)
    tables = jnp.pad(tables, ((0, 0), (0, CFG.max_seq // PAGE - 8)))
    toks = jnp.asarray(_prompt(10, SLOTS), jnp.int32)
    parked = jnp.full((SLOTS,), CFG.max_seq, jnp.int32)
    empty = (jnp.zeros((CHUNK,), jnp.int32), jnp.int32(1), jnp.int32(0),
             jnp.int32(0))
    out = model.step(params, cache, tables, toks, parked, empty, CFG, PAGE)
    for leaf in ("kda", "conv"):
        for a, b in zip(jax.tree.leaves(cache[leaf]),
                        jax.tree.leaves(out[2][leaf])):
            assert (np.asarray(a) == np.asarray(b)).all(), leaf
    # pages: all but the scratch page, which takes every invalid write
    assert (np.asarray(out[2]["kv"][:, :, 1:])
            == np.asarray(cache["kv"][:, :, 1:])).all()
    counts = dict(zip(solar.STEP_COUNTERS, np.asarray(out[3]).tolist()))
    assert counts["expert_picks"] == counts["kda_rows"] == 0
    assert counts["expert_rows"] == 0
    # live rows beside the empty chunk: what the step without a chunk gives
    pos = jnp.asarray([9, 17, CFG.max_seq, 30], jnp.int32)
    alone = model.step(params, cache, tables, toks, pos, None, CFG, PAGE)
    fused = model.step(params, cache, tables, toks, pos, empty, CFG, PAGE)
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(alone[0]),
                               atol=1e-5)
    for a, b in zip(jax.tree.leaves({k: alone[2][k] for k in ("kda", "conv")}),
                    jax.tree.leaves({k: fused[2][k] for k in ("kda", "conv")})):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # the parked row's state was neither read nor written
    assert (np.asarray(fused[2]["kda"][:, 2])
            == np.asarray(cache["kda"][:, 2])).all()
    assert np.asarray(fused[3]).tolist() == np.asarray(alone[3]).tolist()


def test_reused_slot_gives_the_tokens_a_fresh_engine_gives(params):
    first, second = _prompt(4, 30), _prompt(5, 21)
    used = _engine(params, num_slots=2)
    _run(used, first, max_new=9)          # slot 0 used, slot 1 parked
    got = _run(used, second, max_new=7)   # slot 0 again
    fresh = _engine(params, num_slots=2)
    want = _run(fresh, second, max_new=7)
    assert got == want
    assert _gap(params, second, got)["max_gap"] < TOL
    # the parked slot's state was never written, in either engine
    for eng in (used, fresh):
        assert not np.asarray(eng._cache["kda"][:, 1]).any()
        assert all(not np.asarray(c[:, 1]).any() for c in eng._cache["conv"])
    assert np.asarray(used._cache["kda"][:, 0]).any()


# -- (d) a greedy request repeats whatever else is in flight -------------------------

def test_a_repeated_greedy_request_is_deaf_to_its_neighbours(params):
    prompt = _prompt(6, 37)
    alone = _run(_engine(params), prompt, max_new=10)
    engine = _engine(params)

    def never(*a, **kw):
        raise AssertionError("the decode-only program was dispatched")

    engine._decode_only = never           # one_program: the fused one alone
    others = [engine.submit(_prompt(20 + i, 25 + 9 * i), max_new=14)
              for i in range(2)]
    h = engine.submit(prompt, max_new=10)
    late = engine.submit(_prompt(30, 40), max_new=6)
    while not all(x._done.is_set() for x in others + [h, late]):
        assert engine.step()
    assert h.result(timeout=0).tokens == alone
    assert engine.prefix_hits == 0        # a family with slot state takes none


# -- (e) the share the counters read ---------------------------------------------------

def test_local_pick_share_reads_held_over_all_on_uniform_routing():
    """With every expert equally likely, ``expert_rows / expert_picks``
    (``moe.local_pick_share``) is held / all: 4 / 16 here, 20 / 320 =
    6.25 % in the cell."""
    cfg = CFG
    n, k = 4096, cfg.num_experts_per_tok
    rng = np.random.default_rng(0)
    experts = jnp.asarray(np.stack([rng.permutation(cfg.num_experts)[:k]
                                    for _ in range(n)]), jnp.int32)
    p = {"w_gate_up": jnp.zeros((4, cfg.d_model, 2 * cfg.d_expert)),
         "w_down": jnp.zeros((4, cfg.d_expert, cfg.d_model))}
    valid = jnp.ones((n,), bool).at[:96].set(False)
    _, counts = moe.experts_ffn(jnp.zeros((n, cfg.d_model)), experts,
                                jnp.ones((n, k)) / k, valid, p, cfg,
                                held=cfg.experts_held)
    picks = (n - 96) * k
    share = int(counts[1]) / picks
    assert abs(share - 4 / 16) < 0.01
    assert int(counts[0]) == 4 and int(counts[2]) <= int(counts[1])
    from benchmark.readers import counter_share
    with open(os.path.join(ROOT, "benchmark/metrics/"
                           "moe.local_pick_share.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "counter_share"
    got = counter_share.read({"counters": {
        "trace_expert_rows": int(counts[1]), "trace_expert_picks": picks}},
        **spec["args"])
    assert abs(got - 100 * share) < 1e-9
    assert counter_share.read({"counters": {}}, **spec["args"]) is None


# -- (f) the configuration, the cell and its driver -----------------------------------

CELL = "solar-open2-250b.reasoning_closed_1k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_file_is_the_catalog_row_but_for_reduced():
    from benchmark.drivers.serve_solar_replica import solar_config

    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    cfg = cell["config"]
    entry = manifest.configs["solar-open2-250b"]
    assert entry["reduced"] == cfg["reduced"] and \
        sorted(cfg["reduced_why"]) == sorted(cfg["reduced"])
    if os.path.isfile(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "Solar-Open2-250B")
        assert entry["source"] == row["source_url"] == cfg["source"]
        differ = sorted(k for k, v in row["config"].items()
                        if cfg.get(k, "absent") != v)
        assert differ == sorted(cfg["reduced"])
    program = solar_config(cfg)
    published = solar.SolarConfig()
    # every width, the router's width and its picks as published
    for key in ("vocab_size", "d_model", "num_heads", "num_kv_heads",
                "head_dim", "kda_heads", "kda_head_dim", "kda_rank",
                "conv_kernel", "d_expert", "num_experts",
                "num_experts_per_tok", "num_shared_experts", "norm_eps"):
        assert getattr(program, key) == getattr(published, key), key
    # the share and the cut
    assert program.layer_types == solar.PERIOD * 2
    assert program.experts_held == (100, 20) and program.experts_here == 20
    assert program.vocab_here == 24576 == cfg["vocab_size"]
    assert program.max_seq == 1280
    assert cfg["deployment"]["num_slots"] in (128, 112, 96)
    assert cell["chips"] == 1 and cfg["driver"] == "serve_solar"
    assert cfg["reference"] == "solar_open2"


def test_the_manifest_resolves_the_cell_its_traffic_and_its_metrics():
    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    e2e = [m["name"] for m in cell["metrics"]["end_to_end"]]
    assert sorted(e2e) == ["out_tokens_per_s", "setup_s"]
    per_layer = {m["name"] for m in cell["metrics"]["per_layer"]}
    assert {
            # PR 56: the engine loop's own account, one file a metric for
            # the three cells (tests/test_loop_account.py)
            "engine.hole_ms", "engine.caller_cpu_share",
            "engine.submit_p90_ms",
            "step.decode_ms.solar", "step.kda_share",
            "step.attn_share.solar", "kernel.kda_roofline",
            "moe.local_pick_share", "engine.active_slot_share.solar",
            "engine.prefill_wait_share.solar",
            "engine.idle_dispatch_share.solar", "engine.slot_occupancy",
            "step.moe_share", "kernel.moe_roofline",
            "moe.experts_hit_share", "moe.load_max_share"} | {
                # the host's half of a step: the lfm2 twins' readers
                f"engine.{stem}.solar" for stem in (
                    "dispatch_p50_ms", "launch_p50_ms",
                    "dispatch_off_cpu_share", "deliver_off_cpu_share",
                    "idle_unnamed_share", "gc_pause_ms",
                    "compiles_in_trace")} == per_layer
    for m in cell["metrics"]["per_layer"]:
        if m["name"].startswith("engine.") and m["name"].endswith(".solar"):
            twin = m["name"][:-len("solar")] + "lfm2"
            assert manifest.metric_file(m["name"]) == \
                manifest.metric_file(twin)
    traffic = cell["traffic"]
    assert (traffic["clients_per_slot"], traffic["requests_per_client"],
            traffic["shape_seed"], traffic["ramp_s"], traffic["grace_s"],
            traffic["start_stagger_s"], traffic["stream"]) == (
        2, 10, 20260930, 40, 60, 0.1, True)
    plans = [closed_loop.plan(dict(traffic, requests_per_client=2), seed,
                              51.0, cell["config"]["vocab_size"],
                              deployment=cell["config"]["deployment"])
             for seed in (7, 3000003107)]
    sizes = [[(len(r["prompt"]), r["max_tokens"]) for r in p["requests"]]
             for p in plans]
    assert sizes[0] == sizes[1] and plans[0]["clients"] == 256
    assert all(64 <= a <= 256 and 512 <= b <= 1024 for a, b in sizes[0])
    assert all(0 <= t < 24576 for r in plans[1]["requests"]
               for t in r["prompt"])


def test_the_cells_driver_end_to_end_at_a_tiny_size(tmp_path):
    """``benchmark/drivers/serve_solar.py`` as ``benchmark/run.py`` calls
    it, on the CPU: a real replica through ``serve.run`` and HTTP, a tiny
    configuration of this family under a tiny closed loop, the counters,
    the repeated request and the float32 reference deciding ``correct``."""
    import shutil
    import time

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "tools"))
    with open(os.path.join(ROOT,
                           "benchmark/configs/solar-open2-250b.json")) as fh:
        config = json.load(fh)
    config.update(
        name="tiny-solar", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                            "num_heads": 4, "num_kv_heads": None},
        kda_lowrank_width=16, num_hidden_layers=4, gqa_layers=[0],
        n_routed_experts=4, n_routed_experts_published=16,
        experts_held_first=4, num_experts_per_tok=4, vocab_size=256,
        vocab_size_published=512, vocab_held_first=128,
        max_position_embeddings=128, torch_dtype="float32",
        deployment={"num_slots": 4, "page_size": 8, "num_pages": None})
    traffic = {"generator": "closed_loop", "clients_per_slot": 2,
               "requests_per_client": 40, "shape_seed": 5,
               "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
               "output_len": {"dist": "uniform", "min": 8, "max": 24},
               "stream": True, "start_stagger_s": 0.05, "ramp_s": 1.0,
               "grace_s": 30}
    with open(tmp_path / "benchmark/configs/tiny-solar.json", "w") as fh:
        json.dump(config, fh)
    with open(tmp_path / "benchmark/traffic/tiny_decode.json", "w") as fh:
        json.dump(traffic, fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [{"name": "tiny-solar", "source": "test",
                         "file": "benchmark/configs/tiny-solar.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny.decode", "config": "tiny-solar",
                           "traffic": "tiny_decode", "chips": 1,
                           "why": "tiny"}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [dict(m, workloads=["tiny.decode"]) for m in bench[kind]
                       if "workloads" not in m or CELL in m["workloads"]]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)

    from benchmark.manifest import compute_metrics

    manifest = Manifest(str(tmp_path))
    cell = manifest.cell("tiny.decode")
    driver = manifest.load_module("drivers", cell["config"]["driver"])
    out = driver.run(manifest, cell, seed=2**31 + 39, seconds=2.0,
                     trace=False, t0=time.time(), log=lambda s: None,
                     rehearsal=True)
    assert out["correct"], out["notes"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # the state a slot held went to the reference too, and is float32
    held = next(n for n in out["notes"] if n.startswith("reference:"))
    assert "'state_bits': 23" in held and "'state_ok': True" in held
    assert "'state_replay_same': True" in held
    ctx = dict(out["ctx"], config=cell["config"], traffic=cell["traffic"],
               chips=1, seconds=2.0, peaks=manifest.peaks("TPU v5 lite"))
    got = compute_metrics(manifest, cell["metrics"]["end_to_end"], ctx)
    assert got["out_tokens_per_s"]["value"] > 0 and "setup_s" in got
    # untraced, off the chip: no per-layer metric finds anything to read,
    # and none raises for it
    assert compute_metrics(manifest, cell["metrics"]["per_layer"], ctx) == {}


def test_a_checkout_without_the_family_fails_before_the_runtime(monkeypatch):
    from benchmark.drivers import serve_solar

    monkeypatch.setattr(serving, "FAMILIES", ("llama", "lfm2"))
    with pytest.raises(RuntimeError, match="needs the 'solar' serving"):
        serve_solar.run(None, {"config": {"name": "solar-open2-250b"}})


# -- (g) the roofline reader on a hand-made trace --------------------------------------

def test_kda_roofline_and_opsbytes_on_a_hand_made_trace():
    from benchmark.readers import kda_roofline
    from benchmark.trace import opsbytes_kda

    flops, nbytes = opsbytes_kda.row(64, 128, 128)
    # 64 heads x (the 64 KiB state in and out + q, k, g, v, b, o)
    assert nbytes == 64 * 4 * (2 * 128 * 128 + 3 * 128 + 2 * 128 + 1)
    assert flops == 64 * 7 * 128 * 128
    assert opsbytes_kda.row(64, 128, 128, tokens=64)[0] == 64 * flops
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    config = {"linear_attn_config": {"num_heads": 64, "head_dim": 128}}
    # 10 steps in the trace; the counters' interval held 20 steps and
    # 20 x 6 layers x 128 rows; the scope took 10 x 6 x 2 ms
    ctx = {"peaks": peaks, "config": config,
           "counters": {"trace_steps": 20, "trace_kda_rows": 20 * 6 * 128},
           "trace": {"modules": {"jit_block_fn": [0.02] * 10,
                                 "jit_other": [1.0]},
                     "program": {"scopes": {"kda.scan": 0.12},
                                 "busy_s": 0.2}}}
    args = {"scope": "kda.scan", "pattern": "^jit_(block_fn|decode_only_fn)$"}
    least = 10 * 6 * 128 * nbytes / 819e9
    assert kda_roofline.read(ctx, **args) == pytest.approx(
        100 * least / 0.12)
    assert 50 < kda_roofline.read(ctx, **args) < 100
    # a program without the counter, the scope or the layer: nothing read
    for broken in (dict(ctx, counters={"trace_steps": 20}),
                   dict(ctx, config={}),
                   dict(ctx, trace=dict(ctx["trace"], program={
                       "scopes": {}, "busy_s": 0.2})),
                   dict(ctx, trace=None)):
        assert kda_roofline.read(broken, **args) is None


# -- the cell's weights: the bias balanced by the benchmark, not by the family ---

def test_balanced_bias_evens_a_skewed_router_out():
    """Scores with an offset of its own an expert (what a component every
    token shares gives a router): top-4 of 32 loads a few experts many
    times the mean and some not at all; under the balanced bias every
    expert's load is within a quarter of the mean, and the WEIGHTS stay
    the scores' (the bias picks only)."""
    n, e, k = 2048, 32, 4
    key = jax.random.PRNGKey(0)
    offsets = 0.6 * jax.random.normal(key, (e,))
    scores = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(1), (n, e)) + offsets)

    def load(bias):
        _, sel = jax.lax.top_k(scores + bias, k)
        return np.bincount(np.asarray(sel).reshape(-1), minlength=e)

    mean = n * k / e
    raw = load(jnp.zeros((e,)))
    assert raw.max() > 2.5 * mean and raw.min() < 0.3 * mean
    bias = replica.balanced_bias(scores, jnp.zeros((e,)), k)
    even = load(bias)
    assert even.max() < 1.25 * mean and even.min() > 0.75 * mean
    # fresh rows from the same router are even too: it is the offsets
    # the bias cancels, not these rows
    fresh = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(2), (n, e)) + offsets)
    _, sel = jax.lax.top_k(fresh + bias, k)
    again = np.bincount(np.asarray(sel).reshape(-1), minlength=e)
    assert again.max() < 1.4 * mean and again.min() > 0.6 * mean


def test_init_params_is_a_plain_seeded_draw(params):
    """The family draws its bias (0.01 x normal) and balances nothing:
    the same key gives the same weights, and nothing of the program is a
    forward pass over random ids."""
    for p in params["layers"]:
        assert float(jnp.abs(p["expert_bias"].astype(jnp.float32)).max()) \
            < 0.05
    again = solar.init_params(jax.random.PRNGKey(0), CFG)[0]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert not hasattr(solar, "balance_expert_bias")


def test_the_replica_balances_every_layers_bias_by_the_reference(params):
    """``serve_solar_replica.balance_expert_bias`` changes the biases and
    nothing else, by the reference's forward pass alone; under them fresh
    random ids load the 16 experts of every layer more evenly than under
    the seeded draw; the same seed gives the same biases."""
    cfg = dict(REF_CFG, vocab_size=VOCAB)
    balanced = replica.balance_expert_bias(ref, params, cfg, 5,
                                           sequences=16, length=48)
    for p, q in zip(params["layers"], balanced["layers"]):
        assert set(p) == set(q)
        for k in p:
            same = (np.asarray(p[k]) == np.asarray(q[k])).all()
            assert same == (k != "expert_bias"), k
    assert balanced["wte"] is params["wte"]
    geo = ref.geometry(cfg)
    toks = np.random.default_rng(6).integers(0, VOCAB, size=(16, 48))

    def spreads(params):
        out = []
        x = params["wte"][jnp.asarray(toks)].astype(jnp.float32)
        for w, op in zip(params["layers"], ref.layer_types(cfg)):
            w = ref.upcast(w)
            h = jax.vmap(lambda xi: ref.mixed(xi, w, op, geo)[0])(x)
            u = ref.ffn_input(h, w, geo).reshape(-1, CFG.d_model)
            dense = ref.routing(u, w, *geo[4:7])
            load = np.asarray((dense > 0).sum(axis=0))
            out.append(load.max() / load.mean())
            x = h + ref.ffn(u, w, geo[4:]).reshape(h.shape)
        return out

    even, skewed = spreads(balanced), spreads(params)
    assert max(even) < 1.5
    assert np.mean(even) < np.mean(skewed)
    again = replica.balance_expert_bias(ref, params, cfg, 5, sequences=16,
                                        length=48)
    for p, q in zip(balanced["layers"], again["layers"]):
        assert (np.asarray(p["expert_bias"])
                == np.asarray(q["expert_bias"])).all()


# -- the state a slot holds, held to the reference ---------------------------------

def test_recurrence_keeps_the_states_asked_for():
    rng = np.random.default_rng(0)
    n, h, d = 12, 2, 8
    q, k, v, g = (jnp.asarray(rng.normal(size=(n, h, d)), jnp.float32)
                  for _ in range(4))
    g, b = -jnp.abs(g) * 0.1, jnp.asarray(rng.uniform(0, 2, (n, h)),
                                          jnp.float32)
    _, kept = ref.recurrence(q, k, v, g, b, keep=[0, 5, 11, 12])
    assert kept.shape == (4, h, d, d) and not np.asarray(kept[0]).any()
    for i, m in ((1, 5), (2, 11), (3, 12)):
        _, last = ref.recurrence(q[:m], k[:m], v[:m], g[:m], b[:m])
        assert np.abs(np.asarray(kept[i] - last[0])).max() < 1e-6
    # a state rounded to bfloat16 after every token IS rounded (a pair of
    # casts is one a compiler may take out), and differs
    _, low = ref.recurrence(q, k, v, g, b, state_dtype=jnp.bfloat16)
    assert ref.mantissa_bits(low) == 7
    assert ref.mantissa_bits(kept[3]) == 23
    assert 1e-4 < ref.state_error(low, kept[3:]) < 3e-2


@pytest.mark.parametrize("values,bits", [
    (np.float32([1.0, 0.5, 3.0]), 1),
    (np.float32([0.0, 0.0]), 0),
    (np.float32([1.0 + 2.0 ** -23]), 23),
    (np.float32([1.0 + 2.0 ** -10, 2.0]), 10),
    (np.asarray(jnp.linspace(0.1, 3.3, 50).astype(jnp.bfloat16)
                .astype(jnp.float32)), 7),
    (np.asarray(jnp.linspace(0.1, 3.3, 50).astype(jnp.float16)
                .astype(jnp.float32)), 10)])
def test_mantissa_bits(values, bits):
    assert ref.mantissa_bits(values) == bits


def test_the_state_a_slot_holds_is_held_to_the_reference(params):
    """``slot_state_after`` reads what the slot of a finished request
    holds; ``check_generated`` holds it to the reference's state after
    the same tokens and to float32. Both sides are float32 here: the
    error is summation order's. Rounded to bfloat16, zeroed or another
    request's, the state fails the cell's limits."""
    engine = _engine(params).start()
    try:
        other = _run_threaded(engine, _prompt(30, 21), 9)
        prompt = _prompt(31, 37)
        tokens, state = replica.slot_state_after(engine, prompt, [0] * 11)
        again, state2 = replica.slot_state_after(engine, prompt, tokens)
    finally:
        engine.stop()
    assert len(tokens) == 11 and again == tokens and other
    assert state.shape == (3, CFG.kda_heads, 16, 16)
    assert (state == state2).all()     # a reused slot, reset at admission
    sample = {"prompt": prompt, "tokens": tokens}
    res = ref.check_generated(params, REF_CFG, [dict(sample, state=state)])
    assert res["state_err"] < 1e-4 and res["state_bits"] == 23
    assert res["max_gap"] == 0.0
    low = np.asarray(jnp.asarray(state).astype(jnp.bfloat16)
                     .astype(jnp.float32))
    res = ref.check_generated(params, REF_CFG, [dict(sample, state=low)])
    assert res["state_bits"] == 7 < replica.REFERENCE_STATE_BITS
    assert 1e-4 < res["state_err"] < 1e-2
    for wrong in (np.zeros_like(state), state[::-1], 1.3 * state):
        res = ref.check_generated(params, REF_CFG,
                                  [dict(sample, state=wrong)])
        assert res["state_err"] > replica.REFERENCE_STATE_ERR
    # no state given: the comparison of tokens alone, as before
    assert "state_err" not in ref.check_generated(params, REF_CFG, [sample])
