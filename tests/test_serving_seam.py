"""The seam between the serving engine and its model
(``ray_tpu/models/serving.py``): ``llm/`` names no model, a family is
found from the config's type or from a name, and the engine runs whatever
record it finds — here a toy whose cache is not a KV pool at all."""

import ast
import pathlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm.engine import SlotEngine, build_step_programs
from ray_tpu.llm.serve import LLMServer
from ray_tpu.models import llama, serving

PKG = pathlib.Path(ray_tpu.__file__).parent


# -- a toy family --------------------------------------------------------------
# Its cache has two leaves, [num_pages, page_size] each: the tokens written
# and their positions + 1. The next token is a function of everything a
# row's pages hold up to its position, so a wrong page table, a lost copy
# on write or an import that dropped a leaf gives the wrong token.

@dataclass(frozen=True)
class ToyConfig:
    max_seq: int = 64
    vocab_size: int = 101


def toy_next(history):
    """What the toy says after ``history``, on the host."""
    n = len(history)
    return (3 * sum(int(t) for t in history) + n * (n + 1) // 2) % 101


def _toy_write(cache, table_rows, pos, toks, valid, ps):
    """toks[i] at position pos[i] of the row whose table is
    table_rows[i]; invalid ones go to scratch page 0."""
    safe = jnp.clip(pos, 0, table_rows.shape[1] * ps - 1)
    page = jnp.where(
        valid, jnp.take_along_axis(table_rows, (safe // ps)[:, None], 1)[:, 0],
        0)
    off = jnp.where(valid, safe % ps, 0)
    return {"tok": cache["tok"].at[page, off].set(toks),
            "pos1": cache["pos1"].at[page, off].set(safe + 1)}


def _toy_logits(cache, table_rows, upto, vocab):
    """One-hot logits of toy_next over positions <= upto[i] of each row."""
    ps = cache["tok"].shape[1]
    live = jnp.arange(table_rows.shape[1] * ps)[None, :] <= upto[:, None]
    tok = cache["tok"][table_rows].reshape(table_rows.shape[0], -1)
    pos1 = cache["pos1"][table_rows].reshape(table_rows.shape[0], -1)
    nxt = jnp.sum(jnp.where(live, 3 * tok + pos1, 0), axis=1) % vocab
    return jax.nn.one_hot(nxt, vocab, dtype=jnp.float32)


def toy_step(params, cache, tables, tokens, pos, chunk, cfg, page_size,
             rules=None):
    cache = _toy_write(cache, tables, pos, tokens, pos < cfg.max_seq,
                       page_size)
    logits = _toy_logits(cache, tables, pos, cfg.vocab_size)
    if chunk is None:
        return logits, None, cache
    pre_tokens, pre_slot, pre_p0, pre_n_valid = chunk
    c = pre_tokens.shape[0]
    row = jnp.broadcast_to(tables[pre_slot][None], (c, tables.shape[1]))
    at = pre_p0 + jnp.arange(c)
    cache = _toy_write(cache, row, at, pre_tokens,
                       (jnp.arange(c) < pre_n_valid) & (at < cfg.max_seq),
                       page_size)
    pre = _toy_logits(cache, row[:1], (pre_p0 + pre_n_valid - 1)[None],
                      cfg.vocab_size)
    return logits, pre[0], cache


def _toy_check_frames(cache, frames):
    if frames.shape[:2] != (1, 2) or frames.shape[3:] != cache["tok"].shape[1:]:
        raise ValueError(f"toy frames {frames.shape} do not fit")


TOY = serving.ServingModel(
    config_type=ToyConfig, configs={"toy": ToyConfig()},
    init_params=lambda key, cfg: ({"w": jnp.zeros(())}, {"w": ()}),
    param_axes=lambda: {"w": ()},
    check_shardable=lambda cfg, tp: None,
    init_cache=lambda cfg, num_pages, page_size: {
        "tok": jnp.zeros((num_pages, page_size), jnp.int32),
        "pos1": jnp.zeros((num_pages, page_size), jnp.int32)},
    cache_axes={"tok": (None, None), "pos1": (None, None)},
    step=toy_step,
    copy_pages=lambda cache, src, dst: jax.tree.map(
        lambda x: x.at[dst].set(x[src]), cache),
    write_pages=lambda cache, dst, frames: {
        "tok": cache["tok"].at[dst].set(frames[0, 0]),
        "pos1": cache["pos1"].at[dst].set(frames[0, 1])},
    read_pages=lambda cache, idx: np.stack(
        [np.asarray(cache["tok"][idx]), np.asarray(cache["pos1"][idx])])[None],
    check_frames=_toy_check_frames)


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(serving._MODELS, ToyConfig, TOY)
    return ToyConfig()


def toy_reference(prompt, max_new):
    history, out = list(prompt), []
    for _ in range(max_new):
        out.append(toy_next(history))
        history.append(out[-1])
    return out


def toy_engine(cfg, **kw):
    params, _ = TOY.init_params(None, cfg)
    return SlotEngine(params, cfg, num_slots=2, chunk=8, page_size=4,
                      num_pages=40, **kw)


def drain(engine, handles, max_steps=500):
    for _ in range(max_steps):
        if all(h._done.is_set() for h in handles):
            return
        engine.step()
    raise AssertionError("engine did not finish in max_steps")


@pytest.mark.parametrize("decode_block", [1, 3])
def test_engine_serves_a_stub_model(toy, decode_block):
    """Submit, stream and finish on a record the engine has never heard
    of: two requests side by side (one a prefix of the other's pages,
    ending mid-page, so a page is copied on write), streamed from the
    engine's own thread."""
    eng = toy_engine(toy, decode_block=decode_block)
    first = list(range(5, 27))       # 22 tokens: 5 full pages and a half
    drain(eng, [eng.submit(first, max_new=4)])
    eng.start()
    try:
        prompts = [first[:18] + [90, 91, 92], [7, 8, 9]]
        handles = [eng.submit(p, max_new=9) for p in prompts]
        streamed = [list(h) for h in handles]
        for h, got, p in zip(handles, streamed, prompts):
            res = h.result(timeout=60)
            assert got == res.tokens == toy_reference(p, 9)
            assert res.finish_reason == "length"
    finally:
        eng.stop()
    assert eng.prefix_hits == 1 and eng.prefix_tokens_saved >= 16
    assert eng.requests_completed == 3


@pytest.mark.parametrize("temperature", [0.0, 0.7],
                         ids=["greedy", "sampled"])
def test_stub_model_through_the_packed_dispatch(toy, temperature):
    """The third family of tests/test_packed_dispatch.py's first test: a
    record the engine never heard of gets its rows, lane and sampling
    stream through the same one vector a dispatch. Four requests over two
    slots (prompts admitted while the other slot decodes), each token
    drawn as ``engine._sample`` draws it from what the toy says next."""
    eng = toy_engine(toy, prefix_cache=False)
    prompts = [list(range(3, 3 + n)) for n in (13, 5, 22, 9)]
    seeds = [11, 2**31 - 9, 5, 77]
    handles = [eng.submit(p, max_new=7 + i, temperature=temperature, seed=s)
               for i, (p, s) in enumerate(zip(prompts, seeds))]
    drain(eng, handles)
    assert eng.steps_block > 0 and eng.steps_decode_only > 0
    for prompt, seed, h in zip(prompts, seeds, handles):
        history, want = list(prompt), []
        for _ in h.result(timeout=0).tokens:
            tok = toy_next(history)
            if temperature > 0:
                logits = jax.nn.one_hot(tok, toy.vocab_size,
                                        dtype=jnp.float32)
                key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                         len(history))
                tok = int(jax.random.categorical(
                    key, logits / jnp.float32(temperature)))
            want.append(tok)
            history.append(tok)
        assert h.result(timeout=0).tokens == want


def test_stub_model_session_export_import(toy):
    """A session leaves one engine as frames only the record can read
    and continues on another from the imported pages — both leaves of
    them: the next turn is a prefix hit with the right tokens."""
    a, b = toy_engine(toy), toy_engine(toy)
    prompt = list(range(3, 19))      # 16 tokens: 4 full pages
    h = a.submit(prompt, max_new=4, session_id="s")
    drain(a, [h])
    snap = a.export_session("s")
    assert snap["pages_kv"].shape[:3] == (1, 2, snap["covered_tokens"] // 4)
    info = b.import_session(snap)
    assert info["pages_imported"] == snap["covered_tokens"] // 4
    turn2 = prompt + h.result(timeout=0).tokens + [40, 41]
    h2 = b.submit(turn2, max_new=5, session_id="s")
    drain(b, [h2])
    res = h2.result(timeout=0)
    assert res.tokens == toy_reference(turn2, 5)
    assert res.timing["matched_tokens"] >= snap["covered_tokens"]
    bad = dict(snap, pages_kv=snap["pages_kv"][:, :1])
    with pytest.raises(ValueError, match="do not fit"):
        toy_engine(toy).import_session(bad)


def test_a_config_of_no_family_is_refused():
    @dataclass(frozen=True)
    class Orphan:
        max_seq: int = 64

    with pytest.raises(TypeError, match="Orphan"):
        build_step_programs(Orphan(), 4, 1, 2, 8)
    with pytest.raises(KeyError):
        serving.named("no-such-model")


# -- who imports whom ------------------------------------------------------------

def _imports_of(path, node):
    """Dotted names one import statement of the module at ``path`` names,
    relative ones resolved."""
    if isinstance(node, ast.Import):
        yield from (a.name for a in node.names)
    elif isinstance(node, ast.ImportFrom):
        pkg = ".".join(path.relative_to(PKG.parent).parts[:-1])
        base = pkg.rsplit(".", node.level - 1)[0] if node.level else None
        module = ".".join(x for x in (base, node.module) if x)
        yield module
        yield from (f"{module}.{a.name}" for a in node.names)


def _imports(path):
    """Dotted names a module imports, relative ones resolved."""
    for node in ast.walk(ast.parse(path.read_text())):
        yield from _imports_of(path, node)


def test_the_engine_names_no_model_and_models_no_engine():
    """``llm/`` reaches its model through ``models/serving.py`` alone;
    nothing under ``models/`` imports ``llm/``."""
    families = {f"ray_tpu.models.{p.stem}"
                for p in (PKG / "models").glob("*.py")
                if p.stem not in ("__init__", "serving", "common")}
    assert "ray_tpu.models.llama" in families
    llm_imports = {name for p in (PKG / "llm").glob("*.py")
                   for name in _imports(p)}
    assert "ray_tpu.models.serving" in llm_imports
    assert not llm_imports & families, llm_imports & families
    for p in (PKG / "models").glob("*.py"):
        upward = [n for n in _imports(p) if n.startswith("ray_tpu.llm")]
        assert not upward, (p.name, upward)


def _sibling_underscores(path):
    """(sibling, name) for every underscore name of another module of
    ``ray_tpu/models`` that the module at ``path`` imports or reads as an
    attribute of the imported module."""
    siblings = {f"ray_tpu.models.{p.stem}" for p in path.parent.glob("*.py")
                if p != path}
    tree = ast.parse(path.read_text())
    bound = {}                       # local name -> sibling module
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        module, *named = _imports_of(path, node)
        for a, full in zip(node.names, named):
            if full in siblings:
                bound[a.asname or a.name] = full
            elif module in siblings and a.name.startswith("_"):
                yield module, a.name
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and node.attr.startswith("_")
                and isinstance(node.value, ast.Name)
                and node.value.id in bound):
            yield bound[node.value.id], node.attr


def test_no_serving_family_imports_another():
    """A family is a file: deleting or editing one breaks no other. What
    they share (the page pool, a step's rows, the carried window, the
    seeded draws, the expert layer) lies in ``models/step.py``,
    ``common.py`` and ``moe.py``."""
    families = {f"ray_tpu.models.{name}" for name in serving.FAMILIES}
    for name in serving.FAMILIES:
        path = PKG / "models" / f"{name}.py"
        others = families - {f"ray_tpu.models.{name}"}
        crossed = {n for n in _imports(path)
                   if any(n == o or n.startswith(o + ".") for o in others)}
        assert not crossed, (name, sorted(crossed))


def test_no_model_file_reaches_for_a_siblings_private_name():
    """No module under ``models/`` takes a sibling's underscore name, by
    import or as an attribute of the imported module."""
    for path in (PKG / "models").glob("*.py"):
        private = sorted(set(_sibling_underscores(path)))
        assert not private, (path.name, private)


# -- a config registered after import -------------------------------------------

@pytest.mark.parametrize("chunk,lane", [(8, 8), (None, 64)])
def test_llm_server_finds_a_config_registered_late(monkeypatch, chunk, lane):
    """What the benchmark's replica does: put a config into the family's
    own ``CONFIGS`` after everything is imported, then start a server by
    that name — with no scheduling option, as the benchmark passes none:
    the engine then derives its prefill lane (64 on a device with no
    published peaks), and ``stats()`` says the lane and how full it
    ran."""
    cfg = llama.LlamaConfig(vocab_size=256, max_seq=64, num_layers=1,
                            num_heads=2, num_kv_heads=1, d_model=32,
                            d_mlp=64, dtype=jnp.float32, remat=False)
    monkeypatch.setitem(llama.CONFIGS, "late-tiny", cfg)
    found, family = serving.named("late-tiny")
    assert found is cfg and family is serving.model_for(cfg)
    server = LLMServer(model="late-tiny", num_slots=2, chunk=chunk,
                       page_size=8)
    try:
        assert server.engine.cfg is cfg
        prompt = [5, 6, 7, 8, 9]
        got = server.engine.submit(prompt, max_new=6).result(timeout=120)
        stats = server.stats()
        assert stats["prefill_lane"] == lane
        assert stats["prefill_lane_fill"] == pytest.approx(
            stats["prefill_tokens"] / (stats["steps_block"] * lane))
        params, _ = llama.init_params(jax.random.PRNGKey(0), cfg)
        want = llama.generate(params, np.asarray([prompt], np.int32), cfg,
                              max_new=6)
        assert got.tokens == [int(t) for t in np.asarray(want)[0, 5:]]
    finally:
        server.engine.stop()
