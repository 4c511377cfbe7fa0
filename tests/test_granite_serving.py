"""The granite family through the serving engine, at a tiny size on the CPU
(d 64, 8 state-space heads of 16 with a state of 16 a channel, 4 attention
heads of 16, [mamba, mamba, attention, mamba] twice, float32): the
state-space layers' state and the convolution's window a slot beside the
attention layers' pages, the four published multipliers, and the float32
reference all of it is held to
(``benchmark/reference/granite_hybrid.py``). Logits are compared, not
sampled tokens; both sides are float32 here, so only the order of
summation differs and every tolerance is a few float32 ulps of a logit:
the head is the embedding, drawn at 0.02 / 12, so a position's logits
spread by ~1.5e-3 here.
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

from benchmark.drivers import serve_granite_replica as replica  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference import granite_hybrid as ref  # noqa: E402
from benchmark.traffic import closed_loop  # noqa: E402
from ray_tpu.llm.engine import SlotEngine  # noqa: E402
from ray_tpu.models import granite, serving  # noqa: E402
from ray_tpu.ops.ssm_scan import heads_view  # noqa: E402

CFG = granite.CONFIGS["granite-tiny"]
VOCAB = CFG.vocab_size
PAGE, CHUNK, SLOTS = 8, 16, 4
CELL = "granite-4.0-h-micro.rag_closed_1k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# float32 on both sides: summation order alone, on logits that spread by
# ~1.5e-3 (a wrong multiplier moves them by more than 1e-4)
TOL = 2e-7
M, A = granite.MAMBA, granite.ATTENTION
STACKS = {"mixed": CFG.layer_types, "mamba_only": (M,) * 3,
          "attention_only": (A,) * 2, "no_period": (M, A, M, M, M)}


def _ref_cfg(cfg):
    """A program config as the benchmark's configuration file spells it."""
    return {"num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "mamba_n_heads": cfg.ssm_heads, "mamba_d_state": cfg.ssm_state,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "attention_multiplier": cfg.attention_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "layer_types": list(cfg.layer_types)}


REF_CFG = _ref_cfg(CFG)


@pytest.fixture(scope="module")
def params():
    return granite.init_params(jax.random.PRNGKey(0), CFG)[0]


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


def _step_logits(cfg, params, prompt, follow, slot=2):
    """The family's step driven as the engine drives it — the prompt in
    chunks of 16 into ``slot``, then decode rows — -> logits at the end of
    every chunk and at every decode position, and the counts of the last
    step."""
    model = serving.model_for(cfg)
    cache, tables = _fresh_cache(cfg), _tables(slot)
    step = jax.jit(lambda cache, toks, pos, chunk: model.step(
        params, cache, tables, toks, pos, chunk, cfg, PAGE))
    parked = jnp.full((SLOTS,), cfg.max_seq, jnp.int32)
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    got = {}
    for p0 in range(0, len(prompt), CHUNK):
        piece = prompt[p0:p0 + CHUNK]
        buf = np.zeros((CHUNK,), np.int32)
        buf[:len(piece)] = piece
        _, pre, cache, _ = step(cache, zeros, parked, (
            jnp.asarray(buf), jnp.int32(slot), jnp.int32(p0),
            jnp.int32(len(piece))))
        got[p0 + len(piece) - 1] = np.asarray(pre)
    for i, tok in enumerate(follow):
        pos = parked.at[slot].set(len(prompt) + i)
        logits, _, cache, counts = step(cache, zeros.at[slot].set(tok), pos,
                                        None)
        got[len(prompt) + i] = np.asarray(logits[slot])
    return got, np.asarray(counts)


# -- (a) the step against the reference ------------------------------------------

@pytest.mark.parametrize("stack", list(STACKS))
def test_chunked_prefill_then_decode_equals_the_reference_logits(stack):
    """A 39-token prompt in chunks of 16 (every boundary inside a window
    of the 4-tap convolution, the state handed from chunk to chunk), then
    decode rows, gives at every position the logits of the reference's one
    full forward pass: for the tiny period twice, a mamba-only stack, an
    attention-only one and a stack that is no repetition of a period."""
    cfg = dataclasses.replace(CFG, layer_types=STACKS[stack])
    params = granite.init_params(jax.random.PRNGKey(1), cfg)[0]
    prompt, follow = _prompt(1, 39), _prompt(2, 6)
    want = np.asarray(ref.logits(params, _ref_cfg(cfg), prompt + follow))
    assert want.shape == (45, VOCAB) and want.std() > 5e-4
    got, counts = _step_logits(cfg, params, prompt, follow)
    assert sorted(got) == [15, 31, 38] + list(range(39, 45))
    for at, logits in got.items():
        assert np.abs(logits - want[at]).max() < TOL, at
    # one valid row: its state in every mamba layer
    assert counts.tolist() == [cfg.layer_types.count(M)]


FAULTS = {
    "embedding_multiplier": lambda c: dataclasses.replace(
        c, embedding_multiplier=1.0),
    "residual_multiplier": lambda c: dataclasses.replace(
        c, residual_multiplier=1.0),
    "attention_multiplier": lambda c: dataclasses.replace(
        c, attention_multiplier=c.head_dim ** -0.5),
    "logits_scaling": lambda c: dataclasses.replace(c, logits_scaling=1.0),
}


@pytest.mark.parametrize("fault", list(FAULTS) + [
    "conv_bias", "skip", "norm_before_gate"])
def test_what_the_published_description_says_is_held_by_the_reference(
        params, fault, monkeypatch):
    """Each of the four multipliers set to what a family without it
    computes (1, or ``head_dim^-1/2`` for the softmax scale), the
    convolution without its bias, ``y`` without ``D x``, and the norm
    taken BEFORE the gate: every one fails the parity the test above
    holds, by a thousand times its tolerance (the softmax scale by 1800
    times, the others by 13 000 and more)."""
    cfg, p = CFG, params
    if fault in FAULTS:
        cfg = FAULTS[fault](CFG)
    elif fault == "conv_bias":
        p = dict(params, mamba=dict(params[M], conv_b=jnp.zeros_like(
            params[M]["conv_b"])))
    elif fault == "skip":
        p = dict(params, mamba=dict(params[M], d_skip=jnp.zeros_like(
            params[M]["d_skip"])))
    else:
        monkeypatch.setattr(
            granite, "_gated_norm", lambda y, z, scale, eps: granite.rms_norm(
                y, scale, eps) * jax.nn.silu(z.astype(jnp.float32)))
    prompt, follow = _prompt(1, 20), _prompt(2, 2)
    want = np.asarray(ref.logits(params, REF_CFG, prompt + follow))
    got, _ = _step_logits(cfg, p, prompt, follow)
    worst = max(np.abs(logits - want[at]).max() for at, logits in got.items())
    assert worst > 1000 * TOL, (fault, worst)


def test_engine_tokens_lie_on_the_references_argmax(params):
    """Through ``SlotEngine`` itself, several slots at once and an
    admission into a used slot: every generated token's reference logit is
    the position's largest, to summation order; prompts of 45 and 37
    tokens cross two chunk boundaries."""
    engine = _engine(params, num_slots=2)
    prompts = [_prompt(3, 45), _prompt(4, 37), _prompt(5, 21)]
    handles = [engine.submit(p, max_new=12) for p in prompts]
    while not all(h._done.is_set() for h in handles):
        assert engine.step()
    for p, h in zip(prompts, handles):
        tokens = h.result(timeout=0).tokens
        res = _gap(params, p, tokens)
        assert res["n"] == 12 and res["finite"] and res["max_gap"] < TOL
        wrong = [(t + 1) % VOCAB for t in tokens]
        assert _gap(params, p, wrong)["max_gap"] > 1e-4
    # the step's own count arrived with its tokens
    assert engine.ssm_rows > 0 and engine.ssm_rows % 6 == 0
    assert engine.prefix_hits == 0    # a family with slot state takes none


# -- (b) the slot-state contract ---------------------------------------------------

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
    wiped = model.slot_state.reset(cache, jnp.asarray([slot]))
    # reset zeroes that slot's two states and nothing else
    for leaf, axis in (("ssm", 1), ("conv", 2)):
        was, now = np.asarray(cache[leaf]), np.asarray(wiped[leaf])
        assert not np.take(now, slot, axis).any()
        assert np.take(was, slot, axis).any()
        others = [i for i in range(SLOTS) if i != slot]
        assert (np.take(now, others, axis) == np.take(was, others, axis)).all()
        part = dict(cache, **{leaf: wiped[leaf]})
        got = np.asarray(step(part, toks, pos, None)[0][slot])
        # (with D = 1 and step sizes of 0.001-0.1 the state is a few
        # percent of a mixer's output at this size: ten tolerances, not
        # a thousand)
        assert np.abs(got - want).max() > 10 * TOL, leaf
    assert (np.asarray(wiped["kv"]) == np.asarray(cache["kv"])).all()


def test_parked_rows_and_an_empty_chunk_leave_state_and_pages_alone(params):
    """A step whose every row is parked and whose chunk is empty, aimed at
    a slot with live state, returns the cache bit for bit; beside live
    rows an empty chunk changes nothing of what they compute."""
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
    for leaf in ("ssm", "conv"):
        assert (np.asarray(cache[leaf]) == np.asarray(out[2][leaf])).all()
    # pages: all but the scratch page, which takes every invalid write
    assert (np.asarray(out[2]["kv"][:, :, 1:])
            == np.asarray(cache["kv"][:, :, 1:])).all()
    assert np.asarray(out[3]).tolist() == [0]
    # live rows beside the empty chunk: what the step without a chunk gives
    pos = jnp.asarray([9, 17, CFG.max_seq, 30], jnp.int32)
    alone = model.step(params, cache, tables, toks, pos, None, CFG, PAGE)
    fused = model.step(params, cache, tables, toks, pos, empty, CFG, PAGE)
    np.testing.assert_allclose(np.asarray(fused[0]), np.asarray(alone[0]),
                               atol=1e-6)
    for leaf in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(alone[2][leaf]),
                                   np.asarray(fused[2][leaf]), atol=1e-6)
    # the parked row's state was neither read nor written
    assert (np.asarray(fused[2]["ssm"][:, 2])
            == np.asarray(cache["ssm"][:, 2])).all()
    assert (np.asarray(fused[2]["conv"][:, :, 2])
            == np.asarray(cache["conv"][:, :, 2])).all()
    assert np.asarray(fused[3]).tolist() == np.asarray(alone[3]).tolist() \
        == [3 * 6]
    # a chunk's tail past n_valid: the state after 5 of 16 tokens is the
    # state a 5-token chunk leaves
    chunk = jnp.asarray(_prompt(11, CHUNK), jnp.int32)
    part = model.step(params, cache, tables, toks, parked,
                      (chunk, jnp.int32(1), jnp.int32(0), jnp.int32(5)),
                      CFG, PAGE)
    other_tail = chunk.at[5:].set(3)
    same = model.step(params, cache, tables, toks, parked,
                      (other_tail, jnp.int32(1), jnp.int32(0), jnp.int32(5)),
                      CFG, PAGE)
    for leaf in ("ssm", "conv"):
        assert (np.asarray(part[2][leaf]) == np.asarray(same[2][leaf])).all()
    assert not (np.asarray(part[2]["ssm"][:, 1])
                == np.asarray(cache["ssm"][:, 1])).all()


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
        assert not np.asarray(eng._cache["ssm"][:, 1]).any()
        assert not np.asarray(eng._cache["conv"][:, :, 1]).any()
    assert np.asarray(used._cache["ssm"][:, 0]).any()


def test_the_family_is_a_name_in_families_and_a_counter():
    """PR 29's contract: the engine finds the family's record from the
    config's type and names its counter; one program, because hundreds of
    greedy tokens amplify the ulp by which two would differ."""
    cfg, model = serving.named("granite-tiny")
    assert cfg is CFG and model is serving.model_for(CFG)
    assert "granite" in serving.FAMILIES and model.one_program
    assert model.step_counters == ("ssm_rows",)
    assert set(model.step_counters) <= set(SlotEngine.STEP_COUNTERS)
    assert model.slot_state is not None
    whole = granite.CONFIGS["granite-4.0-h-micro"]
    assert granite.layout(whole) == (
        granite.PERIOD, 4, [(M, 0, 5), (A, 0, 1), (M, 5, 4)])
    state = jax.eval_shape(lambda: model.slot_state.attach(
        whole, {}, 64))
    assert state["ssm"].shape == (36, 64, 32, 128, 128)      # 2 MiB a layer
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].shape == (36, 3, 64, 4352)
    assert jax.eval_shape(lambda s: heads_view(s, 64), state["ssm"]).shape \
        == (36, 64, 64, 64, 128)
    shapes = jax.eval_shape(
        lambda: granite.init_params(jax.random.PRNGKey(0), whole)[0])
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert 3.19e9 < count < 3.20e9


def test_init_params_is_a_plain_seeded_draw(params):
    again = granite.init_params(jax.random.PRNGKey(0), CFG)[0]
    other = granite.init_params(jax.random.PRNGKey(1), CFG)[0]
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (params, again, other))):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert not (np.asarray(params["wte"]) == np.asarray(other["wte"])).all()
    mamba = params[M]
    assert mamba["a_log"].dtype == mamba["dt_bias"].dtype == jnp.float32
    decay = np.exp(-np.exp(np.asarray(mamba["a_log"]))
                   * np.log1p(np.exp(np.asarray(mamba["dt_bias"]))))
    assert 0.15 < decay.min() and decay.max() < 1.0   # neither 0 nor 1
    assert (np.asarray(mamba["d_skip"]) == 1).all()
    assert np.asarray(mamba["conv_b"]).std() > 0.05


# -- (c) the configuration, the cell and its driver ----------------------------------

def test_the_configuration_file_is_the_catalog_row_but_for_reduced():
    manifest = Manifest(ROOT)
    cell = manifest.cell(CELL)
    cfg = cell["config"]
    entry = manifest.configs["granite-4.0-h-micro"]
    assert entry["reduced"] == cfg["reduced"] == ["max_position_embeddings"]
    assert sorted(cfg["reduced_why"]) == sorted(cfg["reduced"])
    if os.path.isfile(CATALOG):
        with open(CATALOG) as fh:
            row = next(r for r in map(json.loads, fh)
                       if r["name"] == "granite-4.0-h-micro")
        assert entry["source"] == row["source_url"] == cfg["source"]
        differ = sorted(k for k, v in row["config"].items()
                        if cfg.get(k, "absent") != v)
        assert differ == sorted(cfg["reduced"])
    program = replica.granite_config(cfg)
    published = granite.GraniteConfig()
    # nothing of the model is cut: every field but the positions
    assert dataclasses.replace(program, max_seq=published.max_seq) \
        == published
    assert program.max_seq == 1536 and program.num_layers == 40
    assert cfg["deployment"] == {"num_slots": 64, "page_size": 16,
                                 "num_pages": None, "chunk": 128}
    assert cell["chips"] == 1 and cfg["driver"] == "serve_granite"
    assert cfg["reference"] == "granite_hybrid"
    assert all(isinstance(v, str) and len(v) > 20
               for v in cfg["assumed"].values())


def test_the_manifest_resolves_the_cell_its_traffic_and_its_metrics():
    manifest = Manifest(ROOT)
    assert len(manifest.configs) == 6 and len(manifest.workloads) == 7
    cell = manifest.cell(CELL)
    e2e = [m["name"] for m in cell["metrics"]["end_to_end"]]
    assert sorted(e2e) == ["out_tokens_per_s", "setup_s"]
    per_layer = {m["name"] for m in cell["metrics"]["per_layer"]}
    assert {
            # PR 56: the engine loop's own account, one file a metric for
            # the three cells (tests/test_loop_account.py)
            "engine.hole_ms", "engine.caller_cpu_share",
            "engine.submit_p90_ms",
            "step.decode_ms.granite", "step.ssm_share",
            "step.attn_share.granite", "kernel.ssm_roofline",
            "engine.slot_occupancy"} | {
                f"engine.{stem}.granite" for stem in (
                    "active_slot_share", "prefill_wait_share",
                    "idle_dispatch_share", "idle_unnamed_share",
                    "dispatch_p50_ms", "launch_p50_ms", "gc_pause_ms",
                    "compiles_in_trace")} == per_layer
    for m in cell["metrics"]["per_layer"]:
        if m["name"].startswith("engine.") and m["name"].endswith(".granite"):
            twin = m["name"][:-len("granite")] + "solar"
            assert manifest.metric_file(m["name"]) == \
                manifest.metric_file(twin)
            assert m["workloads"] == [CELL]
    traffic = cell["traffic"]
    assert (traffic["generator"], traffic["clients_per_slot"],
            traffic["requests_per_client"], traffic["grace_s"],
            traffic["start_stagger_s"], traffic["stream"]) == (
        "closed_loop", 2, 12, 60, 0.1, True)
    assert 35 <= traffic["ramp_s"] <= 45
    plans = [closed_loop.plan(dict(traffic, requests_per_client=2), seed,
                              51.0, cell["config"]["vocab_size"],
                              deployment=cell["config"]["deployment"])
             for seed in (7, 3000003107)]
    sizes = [[(len(r["prompt"]), r["max_tokens"]) for r in p["requests"]]
             for p in plans]
    assert sizes[0] == sizes[1] and plans[0]["clients"] == 128
    assert all(256 <= a <= 1024 and 256 <= b <= 512 for a, b in sizes[0])
    assert max(a + b for a, b in sizes[0]) <= 1536
    assert all(0 <= t < 100352 for r in plans[1]["requests"]
               for t in r["prompt"])


def test_the_cells_driver_end_to_end_at_a_tiny_size(tmp_path):
    """``benchmark/drivers/serve_granite.py`` as ``benchmark/run.py`` calls
    it, on the CPU: a real replica through ``serve.run`` and HTTP, a tiny
    configuration of this family under a tiny closed loop, the counters,
    the repeated request and the float32 reference deciding ``correct``."""
    import shutil
    import time

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "tools"))
    with open(os.path.join(
            ROOT, "benchmark/configs/granite-4.0-h-micro.json")) as fh:
        config = json.load(fh)
    config.update(
        name="tiny-granite", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, shared_intermediate_size=128,
        intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        attention_multiplier=0.0625, vocab_size=256,
        max_position_embeddings=128, torch_dtype="float32",
        deployment={"num_slots": 4, "page_size": 8, "num_pages": None})
    traffic = {"generator": "closed_loop", "clients_per_slot": 2,
               "requests_per_client": 40, "shape_seed": 5,
               "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
               "output_len": {"dist": "uniform", "min": 8, "max": 24},
               "stream": True, "start_stagger_s": 0.05, "ramp_s": 1.0,
               "grace_s": 30}
    with open(tmp_path / "benchmark/configs/tiny-granite.json", "w") as fh:
        json.dump(config, fh)
    with open(tmp_path / "benchmark/traffic/tiny_rag.json", "w") as fh:
        json.dump(traffic, fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] = [{"name": "tiny-granite", "source": "test",
                         "file": "benchmark/configs/tiny-granite.json",
                         "reduced": [], "why": "tiny"}]
    bench["workloads"] = [{"name": "tiny.rag", "config": "tiny-granite",
                           "traffic": "tiny_rag", "chips": 1,
                           "why": "tiny"}]
    for kind in ("end_to_end", "per_layer"):
        bench[kind] = [dict(m, workloads=["tiny.rag"]) for m in bench[kind]
                       if "workloads" not in m or CELL in m["workloads"]]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(bench, fh)

    from benchmark.manifest import compute_metrics

    manifest = Manifest(str(tmp_path))
    cell = manifest.cell("tiny.rag")
    driver = manifest.load_module("drivers", cell["config"]["driver"])
    out = driver.run(manifest, cell, seed=2**31 + 43, seconds=2.0,
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
    from benchmark.drivers import serve_granite

    monkeypatch.setattr(serving, "FAMILIES", ("llama", "lfm2", "solar"))
    with pytest.raises(RuntimeError, match="needs the 'granite' serving"):
        serve_granite.run(None, {"config": {"name": "granite-4.0-h-micro"}})


# -- (d) the roofline reader on a hand-made trace --------------------------------------

def test_ssm_roofline_and_opsbytes_on_a_hand_made_trace():
    from benchmark.readers import ssm_roofline
    from benchmark.trace import opsbytes_ssm

    flops, nbytes = opsbytes_ssm.rows(64, 64, 128, rows=1, tokens=1)
    # the 2 MiB state in and out + x, y (64 a head), dt, a (1 a head), B, C
    assert nbytes == 4 * (2 * 64 * 64 * 128 + 64 * (2 * 64 + 2) + 2 * 128)
    assert flops == 64 * 5 * 64 * 128
    more = opsbytes_ssm.rows(64, 64, 128, rows=1, tokens=200)
    assert more[0] == 200 * flops and nbytes < more[1] < 3 * nbytes
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    config = {"layer_types": ["mamba"] * 36 + ["attention"] * 4,
              "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128}
    # 10 steps in the trace; the counters' interval held 20 steps, 8 of
    # them with a chunk of 200 tokens, 64 decode rows in each; the scope
    # took 10 x 36 x 0.6 ms
    rows = 36 * (20 * 64 + 8)
    ctx = {"peaks": peaks, "config": config,
           "counters": {"trace_steps": 20, "trace_steps_block": 8,
                        "trace_prefill_tokens": 1600,
                        "trace_ssm_rows": rows},
           "trace": {"modules": {"jit_block_fn": [0.03] * 4,
                                 "jit_decode_only_fn": [0.02] * 6,
                                 "jit_other": [1.0]},
                     "program": {"scopes": {"ssm.scan": 0.216},
                                 "busy_s": 0.3}}}
    args = {"scope": "ssm.scan", "pattern": "^jit_(block_fn|decode_only_fn)$"}
    tokens = 36 * (20 * 64 + 1600)
    least = 0.5 * opsbytes_ssm.rows(64, 64, 128, rows, tokens)[1] / 819e9
    assert ssm_roofline.read(ctx, **args) == pytest.approx(
        100 * least / 0.216)
    assert 50 < ssm_roofline.read(ctx, **args) < 100
    # a program without the counter, the scope or the layer: nothing read
    for broken in (dict(ctx, counters={"trace_steps": 20}),
                   dict(ctx, config={}),
                   dict(ctx, trace=dict(ctx["trace"], program={
                       "scopes": {}, "busy_s": 0.2})),
                   dict(ctx, trace=None)):
        assert ssm_roofline.read(broken, **args) is None


# -- (e) the reference's own parts -------------------------------------------------------

def test_recurrence_keeps_the_states_asked_for():
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (12, 2, 4))
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (12, 2)) + 2)
    b, c = (jax.random.normal(k, (12, 8)) for k in ks[2:])
    y, kept = ref.recurrence(x, a, b, c, keep=[0, 5, 12])
    assert kept.shape == (3, 2, 4, 8) and not np.asarray(kept[0]).any()
    s = np.zeros((2, 4, 8))
    for t in range(12):
        s = np.asarray(a[t])[:, None, None] * s \
            + np.asarray(x[t])[:, :, None] * np.asarray(b[t])[None, None, :]
        np.testing.assert_allclose(np.asarray(y[t]), s @ np.asarray(c[t]),
                                   atol=1e-5)
        if t + 1 == 5:
            np.testing.assert_allclose(np.asarray(kept[1]), s, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kept[2]), s, atol=1e-5)
    # rounded after every token: bfloat16's 7 mantissa bits
    _, low = ref.recurrence(x, a, b, c, state_dtype=jnp.bfloat16)
    assert ref.mantissa_bits(low) == 7 and ref.mantissa_bits(kept) == 23


def test_the_state_a_slot_holds_is_held_to_the_reference(params):
    """``slot_state_after`` reads what the slot of a finished request
    holds, as ``[mamba layers, heads, head_dim, N]``;
    ``check_generated`` holds it to the reference's state after the same
    tokens and to float32. Both sides are float32 here: the error is
    summation order's. Rounded to bfloat16, zeroed or another layer's, the
    state fails the cell's limits; and the reference with one of the
    cell's faults injected reads a gap or a state error."""
    engine = _engine(params).start()
    try:
        other = engine.submit(_prompt(30, 21), max_new=9).result(
            timeout=120).tokens
        prompt = _prompt(31, 37)
        tokens, state = replica.slot_state_after(engine, prompt, [0] * 11)
        again, state2 = replica.slot_state_after(engine, prompt, tokens)
    finally:
        engine.stop()
    assert len(tokens) == 11 and again == tokens and other
    assert state.shape == (6, CFG.ssm_heads, 16, 16)
    assert (state == state2).all()     # a reused slot, reset at admission
    sample = {"prompt": prompt, "tokens": tokens}
    res = ref.check_generated(params, REF_CFG, [dict(sample, state=state)])
    assert res["state_err"] < 1e-4 and res["state_bits"] == 23
    assert res["max_gap"] == 0.0 and res["logit_std"] > 5e-4
    low = np.asarray(jnp.asarray(state).astype(jnp.bfloat16)
                     .astype(jnp.float32))
    res = ref.check_generated(params, REF_CFG, [dict(sample, state=low)])
    assert res["state_bits"] == 7 < replica.REFERENCE_STATE_BITS
    assert 1e-4 < res["state_err"] < 1e-2
    for wrong in (np.zeros_like(state), state[::-1], 1.3 * state):
        res = ref.check_generated(params, REF_CFG,
                                  [dict(sample, state=wrong)])
        assert res["state_err"] > replica.REFERENCE_STATE_ERR
    # no state given: the comparison of tokens alone
    assert "state_err" not in ref.check_generated(params, REF_CFG, [sample])
    # the faults the probe injects, each seen by the clean engine's sample
    # (the softmax scale moves little at a head of 16: a tenth of that)
    for floor, fault in ((1e-3, {"stale_state": True}),
                         (1e-3, {"drop_skip": True}),
                         (1e-4, {"attention_scale": CFG.head_dim ** -0.5}),
                         (1e-3, {"state_dtype": jnp.bfloat16})):
        res = ref.check_generated(params, REF_CFG,
                                  [dict(sample, state=state)], **fault)
        assert res["max_gap"] > 0 or res["state_err"] > floor, fault
