"""Continuous-batching engine: a PAGED KV cache driven by two compiled
programs, with a radix prefix cache that skips redundant prefill.

Design (TPU-first, static shapes throughout):

- The KV cache is a pool of fixed-size PAGES (the model family's, behind
  ``models/serving.py``: the engine names no model and never looks into
  a page) reached through a per-slot page table, not dense per-slot rows: a
  request whose prompt prefix is already resident borrows those pages
  read-only (refcounted) and starts prefill at the matched length; a
  prefix dying mid-page is copied on write into a fresh page at
  admission. Freed pages return to an LRU free-list; full prompt pages
  are filed in a radix index keyed on page-size token chunks so the
  NEXT turn of a session (or another session sharing the system prompt)
  hits them. PagedAttention (vLLM) + RadixAttention (SGLang) in the
  engine's two-XLA-program style: on the TPU a Pallas kernel reads and
  writes the pages in place (``ops/paged_attention.py``), elsewhere a
  plain scatter and gather.
- The family's one step advances EVERY slot one token per call with
  per-slot positions; idle slots are parked past ``max_seq`` where
  their garbage writes are routed to the reserved scratch page.
- The fused program additionally runs one fixed-size prompt chunk in
  the same params read (chunked prefill), so a long prompt admission
  adds bounded latency to in-flight decodes. The chunk's length is the
  width of the PREFILL LANE, ``chunk``: a caller's number, or with
  ``chunk=None`` what :func:`prefill_lane` derives from the family's
  record, the device's peaks and ``cfg`` — as many rows as the step's
  one read of the weights multiplies for nothing on this chip, where
  only a step with a prompt in hand carries the lane.
- Sampling is fused into both programs and is DETERMINISTIC PER
  REQUEST: token q of a request is drawn with
  ``fold_in(PRNGKey(request_seed), q)``, so a prefix-hit admission
  (fewer prefill dispatches) produces bit-for-bit the same output as a
  cold one — only ``[num_slots]`` int32 tokens cross the device
  boundary per step, never ``[B, vocab]`` logits.
- Towards the device a dispatch is ONE transfer: the rows' overrides,
  positions, temperatures and seeds, the page table and the lane's
  chunk packed into one int32 vector (:class:`HostInputs`) that the
  program takes apart by static slices. The TPU's runtime charges a
  transfer by the call, not by the byte.

Exactly two compiled programs serve any mix of request lengths (for a
family that asks for ``one_program``, the fused one alone); there is no
shape-dependent recompilation after warmup.

Reference intent matched (and exceeded — the reference never touches
the accelerator): ``/root/reference/python/ray/serve/_private/replica.py``
request plane + ``/root/reference/python/ray/serve/batching.py``.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.exceptions import EngineStoppedError
from ..models import serving
from ..observability import tracing
from ..observability.event_stats import EventStats
from ..parallel import sharding as shd
from ..parallel.mesh import DEVICE_PEAKS
from .paged import OverloadedError, PagePool, RadixIndex, llm_metrics

# Interned tag keys for the per-stage histogram (request finish path).
_LLM_STAGE_KEYS = {s: (("stage", s),) for s in
                   ("admission", "queue", "prefix_match", "prefill",
                    "decode")}


def _sample(logits, temps, seeds, qpos):
    """Greedy when temp == 0, else temperature sampling with a
    per-request deterministic stream: token index ``qpos`` of seed ``s``
    always draws from ``fold_in(PRNGKey(s), qpos)`` — independent of
    batching, decode blocking, or how much prefill a prefix hit
    skipped. [B,V] -> [B]."""
    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1)

        def one(lg, t, s, q):
            key = jax.random.fold_in(jax.random.PRNGKey(s), q)
            return jax.random.categorical(key, lg / jnp.maximum(t, 1e-6))

        sampled = jax.vmap(one)(logits, temps, seeds, qpos)
        return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


# The prefill lane where nothing says more: with the decode rows it is
# one 128-row pass of the MXU at up to 64 slots.
_LANE = 64


def prefill_lane(one_program: bool, peaks: Optional[Dict[str, float]],
                 dtype, max_seq: int) -> int:
    """The prefill lane's width, in prompt tokens a step, for an engine
    whose caller named none.

    A step reads every weight once whatever its rows, so until the
    matmuls take as long as that read, further rows are free: the ridge,
    ``flops/s x bytes a weight / (2 x bytes/s)`` rows (240.5 in bfloat16
    on a TPU v5e), here rounded to the nearest power of two. That is the
    lane of a family whose fused program runs only while a prompt is
    pending: a step without a prompt pays nothing for it. A family that
    carries the lane on EVERY step (``one_program``) pays its width as a
    tax on decode, and a device without published peaks has no ridge:
    both get ``_LANE``. The result divides ``max_seq``."""
    lane = _LANE
    if not one_program and peaks is not None:
        ridge = (peaks["bf16_tflops"] * 1e12 * jnp.dtype(dtype).itemsize
                 / (2 * peaks["hbm_gbps"] * 1e9))
        lane = 2 ** round(math.log2(ridge))
    while lane > max_seq or max_seq % lane:
        lane //= 2
    return lane


@dataclass
class GenerationResult:
    tokens: List[int]
    prompt_len: int
    finish_reason: str  # "stop" (eos) | "length"
    # Flight-recorder stage breakdown (seconds): admission_s, queue_s,
    # prefix_match_s, prefill_s, decode_s, decode_per_token_s, total_s,
    # matched_tokens. None when the request errored before finishing.
    timing: Optional[dict] = None


class RequestHandle:
    """Thread-safe consumer side of one generation request.

    Iterating yields token ids as they are produced; ``result()`` blocks
    for the final :class:`GenerationResult`. ``on_token`` (if given at
    submit) is called from the engine thread instead — useful to bridge
    into an asyncio loop without a queue hop.
    """

    def __init__(self, prompt_len: int):
        self._q: "queue.Queue" = queue.Queue()
        self._tokens: List[int] = []
        self._prompt_len = prompt_len
        self._done = threading.Event()
        self._finish_reason = "length"
        self.error: Optional[BaseException] = None
        self.timing: Optional[dict] = None  # set by the engine at finish

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is None:
                if self.error is not None:
                    raise self.error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> GenerationResult:
        if not self._done.wait(timeout):
            raise TimeoutError("generation did not finish in time")
        if self.error is not None:
            raise self.error
        return GenerationResult(tokens=list(self._tokens),
                                prompt_len=self._prompt_len,
                                finish_reason=self._finish_reason,
                                timing=self.timing)

    # engine-side
    def _emit(self, tok: int) -> None:
        self._tokens.append(tok)
        self._q.put(tok)

    def _finish(self, reason: str,
                error: Optional[BaseException] = None) -> None:
        self._finish_reason = reason
        self.error = error
        self._done.set()
        self._q.put(None)


@dataclass
class _Slot:
    handle: RequestHandle
    prompt: np.ndarray  # int32 [prompt_len]
    max_new: int
    temperature: float
    eos_id: Optional[int]
    on_token: Optional[Callable[[Optional[int]], None]]
    seed: int = 0  # per-request sampling stream
    # Chat-session identity: at request finish the engine records the
    # session's transcript so drain can export it (KV page migration)
    # and the crash path can re-prefill it elsewhere.
    session_id: Optional[str] = None
    # (trace_id, parent_span_id) propagated from the serve request; at
    # finish the stage stamps below become child spans on that trace.
    trace_ctx: Optional[tuple] = None
    submit_t: float = 0.0  # monotonic submit time (TTFT + queue timeout)
    # Flight-recorder stamps (monotonic) + measured prefix-match cost:
    # submit -> admit (queue wait) -> first prefill dispatch -> first
    # token -> finish decomposes the request's end-to-end latency.
    admit_t: float = 0.0
    prefill_start_t: float = 0.0
    first_tok_t: float = 0.0
    prefix_match_s: float = 0.0
    prefill_offset: int = 0  # next chunk start; == len(prompt) when done
    matched_len: int = 0  # prompt tokens whose prefill the radix skipped
    pos: int = 0  # write position of the NEXT decode step
    last_token: int = 0
    produced: int = 0
    # Physical pages in logical order; the first ``shared_pages`` are
    # borrowed read-only from the radix index (refcounted, never
    # written), the rest are exclusively owned until freed.
    pages: List[int] = field(default_factory=list)
    shared_pages: int = 0
    inserted: bool = False  # prompt pages filed in the radix index
    # True once this slot's current token lives on-device (row of the
    # previous decode block's `last` output) — its next block input
    # chains device-side with no host round trip.
    on_device_chain: bool = False
    # True between dispatching the FINAL prefill chunk and fetching its
    # sampled first token (lag-1 pipeline): the slot must not join the
    # decode batch until that token is known host-side.
    first_tok_pending: bool = False

    @property
    def prefill_done(self) -> bool:
        return self.prefill_offset >= len(self.prompt)


class HostInputs:
    """Where a dispatch's host-side inputs lie in the ONE int32 vector
    that carries them to the device, ``host_in``: the per-row vectors
    (``rows`` words each, in ``ROWS``' order), the page table (``rows x
    pages_per_seq``), then, for the fused program (``chunk`` given), the
    lane's prompt chunk and its five scalars. On the TPU's runtime a
    transfer costs per call, not per byte (0.25 ms for 64 bytes as for
    256 KiB, PERF.md Findings PR 38), so everything rides one. The packer
    (:meth:`idle`, :meth:`views`) and the two programs (:meth:`unpack`)
    both read the offsets from here, so they cannot drift. A float rides
    as its bits (``temps`` and ``lane_temp`` are float32 views of their
    words on the host and a ``bitcast_convert_type`` in the program: no
    rounding, no convert), the mask as 0 / 1."""

    ROWS = ("override_vals", "override_mask", "pos", "temps", "seeds")
    LANE = ("lane_slot", "p0", "n_valid", "lane_temp", "lane_seed")
    FLOATS = ("temps", "lane_temp")

    def __init__(self, rows: int, pages_per_seq: int,
                 chunk: Optional[int] = None):
        self.table_shape = (rows, pages_per_seq)
        sizes = [(name, rows) for name in self.ROWS]
        sizes.append(("tables", rows * pages_per_seq))
        if chunk is not None:
            sizes += [("pre_tokens", chunk)] + [(n, 1) for n in self.LANE]
        self.fields: Dict[str, slice] = {}
        at = 0
        for name, n in sizes:
            self.fields[name] = slice(at, at + n)
            at += n
        self.size = at

    def idle(self, parked_pos: int) -> np.ndarray:
        """What a dispatch with no active row, no page mapped and an
        empty lane sends: every row takes its (zero) override and sits
        at ``parked_pos``, and the lane's n_valid 0 writes nothing."""
        buf = np.zeros((self.size,), np.int32)
        buf[self.fields["override_mask"]] = 1
        buf[self.fields["pos"]] = parked_pos
        return buf

    def views(self, buf: np.ndarray) -> Dict[str, np.ndarray]:
        """``buf``'s fields by name, each a writable view of its words
        (float32 for the temperatures, ``[rows, pages_per_seq]`` for the
        table)."""
        out = {name: (buf[at].view(np.float32) if name in self.FLOATS
                      else buf[at]) for name, at in self.fields.items()}
        out["tables"] = out["tables"].reshape(self.table_shape)
        return out

    def unpack(self, host_in) -> Dict[str, jax.Array]:
        """The same fields inside a jitted program, by static slices: the
        lane's scalars as scalars, the mask as a bool."""
        if host_in.shape != (self.size,) or host_in.dtype != jnp.int32:
            raise TypeError(f"host_in is {host_in.dtype}{host_in.shape}, "
                            f"this program takes int32[{self.size}]")
        out = {}
        for name, at in self.fields.items():
            x = host_in[at.start] if name in self.LANE else host_in[at]
            if name in self.FLOATS:
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            out[name] = x
        out["override_mask"] = out["override_mask"] != 0
        out["tables"] = out["tables"].reshape(self.table_shape)
        return out


def build_step_programs(cfg, page_size: int, decode_block: int, rows: int,
                        chunk: int, rules=None):
    """The engine's two step programs, un-jitted: ``(block_fn,
    decode_only_fn)``, round the one step of ``cfg``'s family
    (``models/serving.py``). Both take ``(params, cache, prev_last,
    host_in)``: the last block's tokens (which never left the device)
    and ONE int32 vector holding everything a dispatch hands over — the
    rows' vectors, the page table and, for ``block_fn``, the lane — laid
    out as ``HostInputs(rows, max_seq // page_size, chunk)`` for
    ``block_fn``, without ``chunk`` for ``decode_only_fn``, and taken
    apart by static slices before anything else runs. ``SlotEngine``
    jits them with the cache donated; tests/test_tpu_compile.py compiles
    the same two for a described chip from shapes alone.

    A family that names ``step_counters`` returns their counts as a
    fourth result of its step; they leave the program as further columns
    of the block's tokens, ``toks_k [K, slots + len(step_counters)]``, so
    the one fetch that brings the tokens brings them too. A family that
    names none gets the programs it always got."""
    model = serving.model_for(cfg)
    counted = bool(model.step_counters)
    pages_per_seq = cfg.max_seq // page_size
    fused_in = HostInputs(rows, pages_per_seq, chunk)
    decode_in = HostInputs(rows, pages_per_seq)

    def step(*args):
        out = model.step(*args, cfg, page_size, rules)
        return out if counted else out + (None,)

    def with_counts(toks_k, counts_k):
        """[K, slots] tokens and [K, n] counts side by side."""
        return (jnp.concatenate([toks_k, counts_k], axis=1) if counted
                else toks_k)

    def block_fn(params, cache, prev_last, host_in):
        """K-token decode block with the prefill lane fused into the
        FIRST step: a prompt chunk rides the same params read as the
        decode batch, so prefill no longer costs a separate full-model
        pass."""
        h = fused_in.unpack(host_in)
        tables, pos, temps, seeds = (h["tables"], h["pos"], h["temps"],
                                     h["seeds"])
        tokens0 = jnp.where(h["override_mask"], h["override_vals"],
                            prev_last)
        dec_logits, pre_logits, cache, counts = step(
            params, cache, tables, tokens0, pos,
            (h["pre_tokens"], h["lane_slot"], h["p0"], h["n_valid"]))
        tok1 = _sample(dec_logits, temps, seeds, pos + 1)
        pre_tok = _sample(pre_logits[None], h["lane_temp"][None],
                          h["lane_seed"][None],
                          (h["p0"] + h["n_valid"])[None])[0]

        if decode_block == 1:  # nothing to scan: trace no second program
            return (with_counts(tok1[None], counts[None] if counted
                                else None), tok1, pre_tok, cache)

        def body(carry, _):
            toks, cache, p = carry
            logits, _, cache, c = step(params, cache, tables, toks, p, None)
            nxt = _sample(logits, temps, seeds, p + 1)
            return (nxt, cache, p + 1), (nxt, c)

        (last, cache, _), (toks_rest, counts_rest) = jax.lax.scan(
            body, (tok1, cache, pos + 1), None,
            length=decode_block - 1)
        toks_k = jnp.concatenate([tok1[None], toks_rest], axis=0)
        if counted:
            counts_rest = jnp.concatenate([counts[None], counts_rest])
        return with_counts(toks_k, counts_rest), last, pre_tok, cache

    def decode_only_fn(params, cache, prev_last, host_in):
        """Pure K-step decode block — dispatched whenever no prompt
        chunk is pending, so idle steps never pay the fused
        program's C-token prefill lane."""
        h = decode_in.unpack(host_in)
        tables, pos, temps, seeds = (h["tables"], h["pos"], h["temps"],
                                     h["seeds"])
        tokens0 = jnp.where(h["override_mask"], h["override_vals"],
                            prev_last)

        def body(carry, _):
            toks, cache, p = carry
            logits, _, cache, c = step(params, cache, tables, toks, p, None)
            nxt = _sample(logits, temps, seeds, p + 1)
            return (nxt, cache, p + 1), (nxt, c)

        (last, cache, _), (toks_k, counts_k) = jax.lax.scan(
            body, (tokens0, cache, pos), None, length=decode_block)
        return with_counts(toks_k, counts_k), last, cache

    return block_fn, decode_only_fn


# A step that ran this much longer than its kind's typical is a HOLE:
# the ``--long-ms 20`` of ``benchmark/tools/stall_probe.py`` and the size
# ROADMAP [runtime-stalls] speaks of. A constant, not an option.
HOLE_S = 0.020
HOLES_KEPT = 32  # the last holes ``loop_account()`` remembers


class _Typical:
    """What a step of one kind usually takes: the median of the kind's
    first ``SETTLE`` steps (a compile or an empty pipeline among them
    moves no median), then a running mean over about the last 16 steps
    that a hole never enters, so it follows a change of load within a
    fraction of a second and ten holes in a row leave it where it was.
    ``RESEED`` holes in a row are the new load, not holes: the estimate
    starts again."""

    SETTLE, RESEED = 8, 32
    __slots__ = ("mean", "_first", "_streak")

    def __init__(self):
        self.mean: Optional[float] = None  # None until settled
        self._first: List[float] = []
        self._streak = 0

    def over(self, wall: float) -> float:
        """Seconds ``wall`` lies over the typical (0.0 until settled);
        ``wall`` enters the typical unless that makes it a hole."""
        if self.mean is None:
            self._first.append(wall)
            if len(self._first) == self.SETTLE:
                self.mean = sorted(self._first)[self.SETTLE // 2]
                self._first = []
            return 0.0
        over = wall - self.mean
        if over < HOLE_S:
            self._streak = 0
            self.mean += over / 16
        else:
            self._streak += 1
            if self._streak == self.RESEED:
                self.mean, self._streak = None, 0
        return over


def _cpu_clock_of_this_thread() -> Optional[int]:
    """The calling thread's CPU-time clock, which ANY thread may then read
    with ``time.clock_gettime_ns`` (the kernel refuses a thread that is
    gone); None where the platform has none."""
    try:
        return time.pthread_getcpuclockid(threading.get_ident())
    except (AttributeError, OSError):
        return None


class _acquired:
    """``with lock:`` for the engine thread, the wait for the lock an
    ``rt.llm.acquire`` span: between two steps the thread contends with
    ``submit()`` callers for the engine's one lock, and a loop that is
    never out of work shows that wait nowhere else. A wait of ``HOLE_S``
    or more is a hole of its own, its typical zero; the thread's clocks
    are read for it only where the lock was not free."""

    __slots__ = ("_engine", "_lock")

    def __init__(self, engine, lock):
        self._engine = engine
        self._lock = lock

    def __enter__(self):
        engine, clocks = self._engine, None
        with tracing.step_span("rt.llm.acquire",
                               into=engine.account) as sp:
            if not self._lock.acquire(False):
                clocks = engine._read_clocks()
                self._lock.acquire()
        if clocks is not None and sp.seconds >= HOLE_S:
            engine._keep_hole(sp.seconds, 0.0, clocks,
                              engine._since(clocks), "none",
                              {"acquire": sp.seconds})

    def __exit__(self, *exc):
        self._lock.release()
        return False


class SlotEngine:
    """Continuous-batching generation over a paged KV-cache pool."""

    # Serving rule table deltas over parallel.sharding.DEFAULT_RULES:
    # the page pool's heads axis is the KV-heads axis, which the default
    # (training) table leaves replicated — tp-sharded serving maps it to
    # tp so the KV pages (the decode bandwidth bill) split across chips.
    SERVE_RULES = {"kv": "tp"}
    TIMINGS_KEPT = 1024  # finished requests request_timings() remembers
    # cumulative per-step accounting, returned by LLMServer.stats()
    STEP_COUNTERS = ("steps_block", "steps_decode_only", "slot_steps",
                     "slot_steps_active", "slot_steps_prefill_wait",
                     "prefill_tokens", "overshoot_tokens", "kv_pages_read",
                     # what a family's step counts itself (its
                     # ``step_counters``; zero for a family that names
                     # none). Expert layers: experts that got a row, summed
                     # over layers and steps (of held experts x layers x
                     # steps); rows x picks routed; rows of the fullest
                     # expert of each layer; every valid row's picks,
                     # held on this chip or not. Recurrent layers: rows
                     # whose state a step read and wrote, summed over
                     # those layers.
                     "experts_hit", "expert_rows", "expert_rows_max",
                     "expert_picks", "kda_rows", "ssm_rows")

    def __init__(self, params, cfg, num_slots: int = 8,
                 chunk: Optional[int] = None, seed: int = 0,
                 decode_block: int = 1,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 max_pending: Optional[int] = None,
                 queue_timeout_s: Optional[float] = None,
                 max_sessions: int = 256,
                 mesh=None, rules=None):
        model = serving.model_for(cfg)
        if chunk is None:
            device = (jax.devices()[0] if mesh is None
                      else mesh.devices.flat[0])
            chunk = prefill_lane(model.one_program,
                                 DEVICE_PEAKS.get(device.device_kind),
                                 cfg.dtype, cfg.max_seq)
        if cfg.max_seq % chunk != 0:
            raise ValueError(
                f"chunk ({chunk}) must divide max_seq ({cfg.max_seq}): "
                "a padded tail chunk would clamp past the cache end")
        if cfg.max_seq % page_size != 0:
            raise ValueError(
                f"page_size ({page_size}) must divide max_seq "
                f"({cfg.max_seq})")
        self.cfg = cfg
        self._model = model
        self.num_slots = num_slots
        self.chunk = chunk
        self.page_size = page_size
        # decode_block K > 1 amortizes the host<->device round trip: ONE
        # program advances every slot K tokens (an in-program lax.scan
        # chaining sampled tokens device-side), and the host fetches a
        # block's tokens only AFTER dispatching the next block, so the
        # device never waits on the host between blocks (lag-1
        # pipeline). Cost: tokens stream in bursts of K and EOS is
        # noticed up to 2K-1 tokens late (the overshoot is discarded;
        # garbage K/V is overwritten before ever attended).
        self.decode_block = decode_block
        self.max_pending = max_pending
        self.queue_timeout_s = queue_timeout_s
        # Mesh-sharded serving (ROADMAP item 2): with a mesh, params
        # shard by their logical axes (heads/mlp/vocab over tp) and the
        # page pool's KV-heads axis shards over tp — each chip holds
        # 1/tp of the weights AND 1/tp of every KV page, so a model too
        # big for one chip's HBM serves from several. Without a mesh
        # every constraint no-ops and placement is plain device_put.
        self._mesh = mesh
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            model.check_shardable(cfg, sizes.get("tp", 1))
            self._rules = shd.prune_rules_for_mesh(
                mesh, dict(self.SERVE_RULES, **(rules or {})))
            self._params = shd.place(mesh, params, model.param_axes(),
                                     self._rules)
        else:
            self._rules = None
            self._params = jax.device_put(params)
        self._pages_per_seq = cfg.max_seq // page_size
        # Pool default: exactly the dense footprint (num_slots full
        # sequences) plus the single reserved scratch page — the old
        # dense layout burned a whole scratch ROW (max_seq worth of KV)
        # for idle prefill-lane parking; the scratch PAGE costs
        # 1/pages_per_seq of that. Larger pools leave headroom for the
        # radix index to keep evicted sessions' prefixes warm.
        self._num_pages = (num_pages if num_pages is not None
                           else num_slots * self._pages_per_seq + 1)
        self._pool = PagePool(self._num_pages)
        # A family with per-slot state takes no prefix hit: a page does
        # not hold the state at its boundary (models/serving.py), so
        # there is no index, every prompt prefills from position 0, and
        # a session travels as its transcript.
        self._radix: Optional[RadixIndex] = (
            RadixIndex(self._pool, page_size)
            if prefix_cache and model.slot_state is None else None)
        self._tables = np.zeros((num_slots, self._pages_per_seq),
                                dtype=np.int32)
        self._cache = model.init_cache(cfg, self._num_pages, page_size)
        if model.slot_state is not None:
            self._cache = model.slot_state.attach(cfg, self._cache,
                                                  num_slots)
        if mesh is not None:
            self._cache = shd.place(mesh, self._cache, model.cache_axes,
                                    self._rules)
        self._base_seed = seed
        self._req_counter = 0
        block_fn, decode_only_fn = build_step_programs(
            cfg, page_size, decode_block, num_slots, chunk, self._rules)
        # What a dispatch packs its rows, the page table and the lane
        # into, by whether the program is the fused one: the layout, and
        # the vector of a dispatch with nothing in it. Each dispatch fills
        # a COPY: a transfer may still be reading the last one (on the CPU
        # backend the device array may alias it outright). Parked rows sit
        # AT max_seq: the paged scatter routes any write at pos >= max_seq
        # to the scratch page, so a parked row can never touch a live
        # (possibly shared) page.
        self._host_in = {
            fused: (layout, layout.idle(cfg.max_seq))
            for fused, layout in (
                (True, HostInputs(num_slots, self._pages_per_seq, chunk)),
                (False, HostInputs(num_slots, self._pages_per_seq)))}

        # The cache is donated, and as compiled for the TPU a step
        # touches it only in place: the layer loop carries the pool whole
        # and the paged-attention kernel, to which the pool is aliased,
        # writes the new rows into their pages and reads the pages in
        # use (tests/test_tpu_compile.py holds both programs to that: no
        # pool-shaped copy, temporaries a small fraction of the pool).
        # Off the TPU the reference path scatters and gathers instead.
        # Under a mesh, every compiled-program call is wrapped so
        # constrain() resolves (ambient mesh + current-mesh global); the
        # constraints pin the output cache to the input's sharding, so
        # donation stays an in-place aliasing across steps.
        def _maybe_mesh(fn):
            return fn if mesh is None else shd.under_mesh(mesh, fn)

        self._block = _maybe_mesh(jax.jit(block_fn, donate_argnums=(1,)))
        self._decode_only = _maybe_mesh(
            jax.jit(decode_only_fn, donate_argnums=(1,)))
        self._copy_pages = _maybe_mesh(
            jax.jit(model.copy_pages, donate_argnums=(0,)))
        # Session import (page migration): compiled lazily on first use
        # from the engine thread's control-op slot, where no concurrent
        # dispatch can be touching the donated cache.
        self._write_pages = _maybe_mesh(
            jax.jit(model.write_pages, donate_argnums=(0,)))
        # Pre-compile the COW page-copy program NOW, while no engine
        # thread can be touching the (donated) cache: the first partial
        # prefix hit must not stall on a compile, and compiling from
        # warmup() would race a running engine thread's dispatches.
        zero = jnp.zeros((1,), jnp.int32)
        self._cache = self._copy_pages(self._cache, zero, zero)
        # Per-slot state is zeroed at admission, by a program of its own
        # that runs between two steps; compiled now, like the page copy.
        self._reset_slots = None
        if model.slot_state is not None:
            self._reset_slots = _maybe_mesh(
                jax.jit(model.slot_state.reset, donate_argnums=(0,)))
            self._cache = self._reset_slots(self._cache, zero)
        unknown = set(model.step_counters) - set(self.STEP_COUNTERS)
        if unknown:
            raise ValueError(f"step counters {sorted(unknown)} are not "
                             "among SlotEngine.STEP_COUNTERS")
        # lag-1 decode pipeline state
        self._inflight = None  # (snapshot, pre_info, toks_k, pre_tok)
        self._last_dev = jnp.zeros((num_slots,), jnp.int32)

        self._slots: List[Optional[_Slot]] = [None] * num_slots
        self._pending: deque = deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # Resident chat sessions (LRU-bounded): session_id ->
        # {transcript, seed, temperature, t}. The KV pages themselves
        # live in the radix index; this is the metadata that lets
        # export_session find them and the crash path re-prefill.
        self.max_sessions = max_sessions
        self._sessions: "OrderedDict[str, dict]" = OrderedDict()
        # Control ops (export/import/...) run ON THE ENGINE THREAD at a
        # step boundary: the cache is donated to compiled programs and
        # mutated by the dispatch path outside the lock, so another
        # thread must never touch it directly.
        self._control: deque = deque()
        # counters (observability / autoscaling signals)
        self.tokens_generated = 0
        self.requests_completed = 0
        self.requests_shed = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_saved = 0
        # Per-step accounting, cumulative; the rt.llm.step span carries
        # the same counts for one step. A slot-step is one slot's place
        # in one decode step of a dispatched program: slot_steps counts
        # all of them, _active those that decoded a token, _prefill_wait
        # those of a slot whose prompt was not through yet. Once nothing
        # is in flight, tokens_generated + overshoot_tokens ==
        # slot_steps_active + one first token per finished prefill.
        self.steps_block = 0
        self.steps_decode_only = 0
        self.slot_steps = 0
        self.slot_steps_active = 0
        self.slot_steps_prefill_wait = 0
        self.prefill_tokens = 0
        self.overshoot_tokens = 0
        # KV pages the dispatched rows attend over, a layer: the sum of
        # ceil(length / page_size) over every decode row of every decode
        # step of a block and the prefill lane's slot. On the TPU it is
        # what the paged-attention kernel reads; against slot_steps x
        # pages_per_seq it is the live share of what gathering every
        # table entry reads.
        self.kv_pages_read = 0
        self.experts_hit = self.expert_rows = self.expert_rows_max = 0
        self.expert_picks = self.kda_rows = self.ssm_rows = 0
        self._callbacks = 0  # on_token calls made delivering tokens
        # The loop's own time account, always on (no profiler, no ring):
        # every ``rt.llm.*`` stage's count, seconds and longest run, added
        # by the stage's own span (``step_span(.., into=)``), and the
        # steps that ran HOLE_S or more over their kind's typical, each
        # kept with what the step was made of. ``loop_account()`` reads it.
        self.account = EventStats()
        self.holes = 0      # cumulative, like steps_block
        self.hole_s = 0.0   # seconds over the typical, summed over holes
        self._holes: deque = deque(maxlen=HOLES_KEPT)
        self._typical = {"block": _Typical(), "decode_only": _Typical()}
        self._steps = 0  # rt.llm.step spans opened: a hole's ``step``
        # the thread that last called submit() (a replica's event loop)
        # and its CPU-time clock, which this loop reads at a step's ends
        self._caller: Optional[int] = None
        self._caller_clock: Optional[int] = None
        # The last finished requests' timing: a streamed response
        # carries tokens only, so this is where its stages are read.
        self._timings: deque = deque(maxlen=self.TIMINGS_KEPT)

    # -- public API --------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new: int = 64,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               on_token: Optional[Callable[[Optional[int]], None]] = None,
               seed: Optional[int] = None,
               session_id: Optional[str] = None,
               trace_ctx: Optional[tuple] = None) -> RequestHandle:
        prompt = np.asarray(prompt, dtype=np.int32)
        if prompt.ndim != 1 or len(prompt) == 0:
            raise ValueError("prompt must be a non-empty 1D token list")
        if len(prompt) + max_new > self.cfg.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"max_seq ({self.cfg.max_seq})")
        n_total = -(-(len(prompt) + max_new) // self.page_size)
        if n_total > self._num_pages - 1:
            # Admission reserves the worst-case footprint; a request the
            # pool can never cover would head-of-line block the FIFO
            # queue forever. Reject it at the door instead.
            raise ValueError(
                f"request needs {n_total} KV pages but the pool only "
                f"has {self._num_pages - 1} allocatable")
        if trace_ctx is None:
            # Direct submits (no serve hop) still join a caller's trace
            # when one is open on this thread / task.
            trace_ctx = tracing.inject_context()
        handle = RequestHandle(len(prompt))
        slot = _Slot(handle=handle, prompt=prompt, max_new=max_new,
                     temperature=float(temperature), eos_id=eos_id,
                     on_token=on_token, submit_t=time.monotonic(),
                     session_id=session_id, trace_ctx=trace_ctx)
        ident = threading.get_ident()
        if ident != self._caller:
            self._caller_clock = _cpu_clock_of_this_thread()
            self._caller = ident
        # The counterpart of rt.llm.acquire, on the CALLER's thread: its
        # wait for the engine's lock and what it does holding it.
        with tracing.step_span("rt.llm.submit", into=self.account), \
                self._work:
            if (self.max_pending is not None
                    and len(self._pending) >= self.max_pending):
                self.requests_shed += 1
                raise OverloadedError(
                    f"engine overloaded: {len(self._pending)} requests "
                    f"pending (max_pending={self.max_pending})")
            self._req_counter += 1
            # Masked to int32 range either way: the seed rides a
            # np.int32 vector into the compiled program, and an
            # out-of-range user seed must not OverflowError the engine
            # thread (which would fail every tenant's request).
            slot.seed = (int(seed) if seed is not None else
                         self._base_seed * 1000003
                         + self._req_counter) & 0x7FFFFFFF
            self._pending.append(slot)
            self._work.notify()
        return handle

    def start(self) -> "SlotEngine":
        if self._thread is None:
            # what stops this loop from outside it, as spans and counts
            tracing.watch_gc()
            tracing.watch_compiles()
            self._thread = threading.Thread(target=self._run,
                                            name="llm-engine", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        with self._work:
            self._stop = True
            self._work.notify()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # Whether or not a thread ever ran (or won the race to drain),
        # no caller may be left hanging: flush queued control ops and
        # fail any still-registered request with the typed error.
        with self._lock:
            self._drain_control_locked()
            self._fail_all_locked(EngineStoppedError("engine stopped"))

    def warmup(self) -> None:
        """Compile both programs before serving traffic. Safe to call
        whether or not the engine thread is running."""
        h = self.submit([1, 2, 3], max_new=2)
        if self._thread is not None:
            h.result(timeout=600)
            return
        while not h._done.is_set():
            if not self.step():
                break
        h.result(timeout=0)

    # -- paged-pool introspection -----------------------------------------

    @property
    def pages_total(self) -> int:
        return self._pool.num_pages

    @property
    def pages_used(self) -> int:
        return self._pool.used_count

    @property
    def pages_free(self) -> int:
        return self._pool.free_count

    @property
    def prefill_lane_fill(self) -> float:
        """Prompt tokens the lane carried over the tokens it had room
        for, in the steps that had a prompt in it: ``prefill_tokens /
        (steps_block x chunk)``. Low where prompts are short beside the
        lane or end in a mostly empty chunk."""
        return self.prefill_tokens / max(1, self.steps_block * self.chunk)

    def prefix_cache_len(self) -> int:
        return 0 if self._radix is None else len(self._radix)

    def clear_prefix_cache(self) -> int:
        """Drop every radix entry (and the pages only it held). Returns
        pages freed; used for cold-run benching and tests."""
        with self._lock:
            freed = 0 if self._radix is None else self._radix.clear()
            self._publish_page_gauges()
            return freed

    def _publish_page_gauges(self) -> None:
        m = llm_metrics()
        if m is not None:
            m["pages_used"].set(float(self._pool.used_count))
            m["pages_free"].set(float(self._pool.free_count))

    # -- stateful sessions (migration & drain) -----------------------------

    def sessions(self) -> List[str]:
        """Resident session ids (insertion/LRU order, oldest first)."""
        with self._lock:
            return list(self._sessions.keys())

    @property
    def session_count(self) -> int:
        with self._lock:
            return len(self._sessions)

    def _record_session_locked(self, session_id: str, transcript,
                               seed, temperature: float) -> None:
        self._sessions[session_id] = {
            "transcript": np.asarray(transcript, dtype=np.int32),
            "seed": int(seed or 0) & 0x7FFFFFFF,
            "temperature": float(temperature),
            "t": time.monotonic(),
        }
        self._sessions.move_to_end(session_id)
        while len(self._sessions) > self.max_sessions:
            self._sessions.popitem(last=False)
        m = llm_metrics()
        if m is not None:
            m["sessions_resident"].set(float(len(self._sessions)))

    def _run_control(self, fn, timeout: float = 60.0):
        """Run ``fn`` under the engine lock ON THE ENGINE THREAD at a
        step boundary. The KV cache is donated to the compiled programs
        and reassigned by the dispatch path OUTSIDE the lock, so a
        foreign thread must never read or write it directly; with no
        engine thread running the caller becomes the executor."""
        thread = self._thread
        if (thread is None or not thread.is_alive()
                or thread is threading.current_thread()):
            with self._lock:
                return fn()
        box: dict = {}
        done = threading.Event()

        def op():
            try:
                box["result"] = fn()
            except BaseException as e:  # noqa: BLE001 — relayed below
                box["error"] = e
            finally:
                done.set()

        with self._work:
            self._control.append(op)
            self._work.notify()
        if not done.wait(timeout):
            raise TimeoutError("engine control op timed out")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def export_session(self, session_id: str) -> dict:
        """Snapshot a session between decode steps: transcript, sampling
        seed, and the radix-resident KV pages covering its prefix packed
        page-major into ONE contiguous frame — shipped zero-copy by the
        object plane (``put_frame`` lays out-of-band buffers 64B-aligned
        in the frame). Raises KeyError for an unknown session and
        RuntimeError while the session has a generation in flight."""
        return self._run_control(
            lambda: self._export_session_locked(session_id))

    def _export_session_locked(self, session_id: str) -> dict:
        sess = self._sessions.get(session_id)
        if sess is None:
            raise KeyError(f"unknown session {session_id!r}")
        live = [s for s in self._slots if s is not None]
        for s in list(self._pending) + live:
            if s.session_id == session_id:
                raise RuntimeError(
                    f"session {session_id!r} has a generation in flight")
        transcript = sess["transcript"]
        pages: List[int] = []
        if self._radix is not None:
            pages, _ = self._radix.match(transcript)
        frames = None
        if pages:
            # Device gather -> host; pages stay index-owned (we hold
            # the lock, so no concurrent eviction can free them).
            frames = self._model.read_pages(
                self._cache, np.asarray(pages, dtype=np.int32))
        m = llm_metrics()
        if m is not None:
            m["session_migrations"].inc(tags={"result": "export"})
        return {
            "session_id": session_id,
            "transcript": np.asarray(transcript, dtype=np.int32),
            "seed": sess["seed"],
            "temperature": sess["temperature"],
            "page_size": self.page_size,
            "covered_tokens": len(pages) * self.page_size,
            "pages_kv": frames,
        }

    def import_session(self, snapshot: dict) -> dict:
        """Rebuild an exported session on THIS engine: prefix chunks
        already present in the local radix index are re-matched (COW
        borrow — never shipped twice), the rest are scattered into
        freshly allocated pages and filed in the index. Runs out of
        pool room -> partial import (the uncovered tail simply
        re-prefills on the session's next turn)."""
        return self._run_control(
            lambda: self._import_session_locked(dict(snapshot)))

    def _import_session_locked(self, snap: dict) -> dict:
        ps = self.page_size
        m = llm_metrics()
        try:
            if int(snap["page_size"]) != ps:
                raise ValueError(
                    f"page_size mismatch: snapshot {snap['page_size']} "
                    f"vs engine {ps}")
            transcript = np.asarray(snap["transcript"], dtype=np.int32)
            frames = snap.get("pages_kv")
            if frames is not None and self._model.slot_state is not None:
                raise serving.SlotStateError(
                    "the snapshot carries KV pages, but this model keeps "
                    "state a slot beside its pages and a page does not "
                    "hold the state at its boundary: export the session "
                    "from an engine of this family (transcript only) or "
                    "drop 'pages_kv', and it re-prefills here")
            n_chunks = int(snap.get("covered_tokens", 0)) // ps
            matched: List[int] = []
            fresh: List[int] = []
            if (self._radix is not None and n_chunks > 0
                    and frames is not None):
                self._model.check_frames(self._cache, frames)
                matched, _ = self._radix.match(transcript[:n_chunks * ps])
                need = n_chunks - len(matched)
                if need > 0 and self._pool.free_count < need:
                    self._radix.evict(need - self._pool.free_count)
                fresh = [self._pool.alloc() for _ in
                         range(min(max(0, need), self._pool.free_count))]
                if fresh:
                    have = len(matched)
                    self._write_frames_locked(
                        fresh, frames[:, :, have:have + len(fresh)])
                pages = matched + fresh
                if pages:
                    self._radix.insert(transcript[:len(pages) * ps],
                                       pages)
                # insert() took the index's own refs on NEW nodes; drop
                # our allocation refs so the index is the sole owner
                # and normal LRU eviction applies.
                for pg in fresh:
                    self._pool.unref(pg)
                self._publish_page_gauges()
            self._record_session_locked(
                snap["session_id"], transcript, snap.get("seed", 0),
                snap.get("temperature", 0.0))
        except Exception:
            if m is not None:
                m["session_migrations"].inc(tags={"result": "error"})
            raise
        if m is not None:
            m["session_migrations"].inc(tags={"result": "import"})
        return {"session_id": snap["session_id"],
                "pages_imported": len(fresh),
                "pages_matched": len(matched),
                "tokens_resident": (len(matched) + len(fresh)) * ps}

    def _write_frames_locked(self, pages: List[int], frames) -> None:
        """Scatter host KV frames into device pages. N is padded to the
        next power of two — padding rows aim at the reserved scratch
        page 0, which absorbs them — so repeated imports compile at
        most O(log pool) program variants."""
        n = len(pages)
        bucket = 1
        while bucket < n:
            bucket *= 2
        dst = np.zeros((bucket,), dtype=np.int32)
        dst[:n] = pages
        vals = np.zeros(frames.shape[:2] + (bucket,) + frames.shape[3:],
                        dtype=frames.dtype)
        vals[:, :, :n] = frames[:, :, :n]
        self._cache = self._write_pages(self._cache, jnp.asarray(dst),
                                        jnp.asarray(vals))

    def prefill_session(self, session_id: str, transcript,
                        seed=None, temperature: float = 0.0,
                        timeout: float = 120.0) -> dict:
        """Crash-path recovery: rebuild a session the cheap-but-correct
        way by re-prefilling its transcript (radix hit -> near no-op,
        cold -> one full prefill). The single sampled token is
        discarded; the transcript's pages land in the radix index so
        the session's next turn admits warm. Publishes
        ``rt_llm_session_recovery_seconds``."""
        t0 = time.monotonic()
        toks = np.asarray(transcript, dtype=np.int32)
        if toks.ndim != 1 or len(toks) == 0:
            raise ValueError("transcript must be a non-empty token list")
        toks = toks[:self.cfg.max_seq - 1]
        h = self.submit(toks, max_new=1,
                        seed=None if seed is None else int(seed))
        if self._thread is not None and self._thread.is_alive():
            h.result(timeout=timeout)
        else:
            while not h._done.is_set():
                if not self.step():
                    break
        res = h.result(timeout=0)
        with self._lock:
            self._record_session_locked(
                session_id, np.asarray(transcript, dtype=np.int32),
                seed, temperature)
        dt = time.monotonic() - t0
        m = llm_metrics()
        if m is not None:
            m["session_recovery"].observe(dt)
        return {"session_id": session_id, "seconds": dt,
                "matched_tokens": (res.timing or {}).get(
                    "matched_tokens", 0),
                "transcript_len": int(len(toks))}

    # -- engine loop -------------------------------------------------------

    def _run(self) -> None:
        while True:
            with _acquired(self, self._work):
                while not self._stop and not self._has_work_locked():
                    with tracing.step_span("rt.llm.wait_work",
                                           into=self.account):
                        self._work.wait()
                if self._stop:
                    self._drain_control_locked()
                    self._fail_all_locked(
                        EngineStoppedError("engine stopped"))
                    return
            try:
                self.step()
            except Exception as e:  # noqa: BLE001 — device fault is fatal
                with self._work:
                    self._drain_control_locked()
                    self._fail_all_locked(e)
                return

    def _has_work_locked(self) -> bool:
        return (bool(self._pending) or self._inflight is not None
                or bool(self._control)
                or any(s is not None for s in self._slots))

    def _drain_control_locked(self) -> None:
        # Control-op wrappers trap their own exceptions into the
        # caller's result box, so draining never throws.
        while self._control:
            self._control.popleft()()

    def _release_slot_pages_locked(self, s: _Slot) -> None:
        for pg in s.pages:
            self._pool.unref(pg)
        s.pages = []
        s.shared_pages = 0

    def _fail_all_locked(self, err: BaseException) -> None:
        self._inflight = None
        for i, s in enumerate(self._slots):
            if s is not None:
                self._release_slot_pages_locked(s)
                self._tables[i] = 0
                s.handle._finish("error", err)
                if s.on_token:
                    s.on_token(None)
                self._slots[i] = None
        while self._pending:
            s = self._pending.popleft()
            s.handle._finish("error", err)
            if s.on_token:
                s.on_token(None)
        self._publish_page_gauges()

    # -- admission (paged + radix match) -----------------------------------

    def _shed_expired_locked(self) -> None:
        if self.queue_timeout_s is None:
            return
        now = time.monotonic()
        while self._pending and (now - self._pending[0].submit_t
                                 > self.queue_timeout_s):
            s = self._pending.popleft()
            self.requests_shed += 1
            s.handle._finish("error", OverloadedError(
                f"engine overloaded: request queued longer than "
                f"queue_timeout_s={self.queue_timeout_s}"))
            if s.on_token:
                s.on_token(None)

    def _admit_locked(self, idx: int, s: _Slot) -> bool:
        """Install a pending request into slot ``idx``: radix-match its
        prompt, borrow the matched pages read-only, COW-copy a partial
        tail page, and eagerly allocate the rest of its worst-case
        footprint (prompt + max_new). Returns False — leaving the
        request pending, FIFO order preserved — when even after LRU
        eviction the pool cannot cover it."""
        ps = self.page_size
        n_total = -(-(len(s.prompt) + s.max_new) // ps)
        full_pages: List[int] = []
        partial = None
        if self._radix is not None:
            match_t0 = time.monotonic()
            full_pages, partial = self._radix.match(s.prompt)
            s.prefix_match_s = time.monotonic() - match_t0
            # The engine needs the LAST prompt token's logits to sample
            # the first output, so at least one prompt token must
            # prefill: cap the match at len(prompt) - 1.
            while len(full_pages) * ps >= len(s.prompt):
                full_pages.pop()
                partial = None
            if partial is not None:
                cap = len(s.prompt) - 1 - len(full_pages) * ps
                if min(partial[1], cap) <= 0:
                    partial = None
                else:
                    partial = (partial[0], min(partial[1], cap))
        # Borrow refs BEFORE any eviction so the matched nodes stop
        # being eviction candidates (their refcount leaves 1).
        for pg in full_pages:
            self._pool.ref(pg)
        if partial is not None:
            self._pool.ref(partial[0])
        n_fresh = n_total - len(full_pages)
        if self._pool.free_count < n_fresh and self._radix is not None:
            self._radix.evict(n_fresh - self._pool.free_count)
        if self._pool.free_count < n_fresh and partial is not None:
            # A full-page borrow is feasibility-neutral (it pins one
            # page but also saves one fresh page), but the partial
            # borrow pins its source WITHOUT reducing n_fresh — the COW
            # copy lands in a fresh page. For a request whose footprint
            # needs the whole pool that pin makes admission impossible
            # forever (the pinned page can never be evicted), so drop
            # the partial match and retry before giving up.
            self._pool.unref(partial[0])
            partial = None
            if self._radix is not None:
                self._radix.evict(n_fresh - self._pool.free_count)
        if self._pool.free_count < n_fresh:
            for pg in full_pages:  # rollback the borrow; stay pending
                self._pool.unref(pg)
            if partial is not None:
                self._pool.unref(partial[0])
            return False
        fresh = [self._pool.alloc() for _ in range(n_fresh)]
        s.pages = full_pages + fresh
        s.shared_pages = len(full_pages)
        s.matched_len = len(full_pages) * ps
        if partial is not None:
            # Copy-on-write: the borrowed page's first n tokens are
            # reused, but this slot will write the rest of that page —
            # device-copy it into the slot's own fresh page, then drop
            # the temporary borrow ref.
            src, n_tok = partial
            dst = fresh[0]
            self._cache = self._copy_pages(
                self._cache, jnp.asarray([src], jnp.int32),
                jnp.asarray([dst], jnp.int32))
            self._pool.unref(src)
            s.matched_len += n_tok
        self._tables[idx, :n_total] = s.pages
        self._tables[idx, n_total:] = 0
        if self._reset_slots is not None:
            # whatever the slot's last request (or a block still in
            # flight for it) left there is void for this one
            self._cache = self._reset_slots(
                self._cache, jnp.asarray([idx], jnp.int32))
        s.prefill_offset = s.matched_len
        s.pos = 0
        hit = s.matched_len > 0
        if hit:
            self.prefix_hits += 1
            self.prefix_tokens_saved += s.matched_len
        else:
            self.prefix_misses += 1
        m = llm_metrics()
        if m is not None:
            m["prefix"].inc(tags={"result": "hit" if hit else "miss"})
            if hit:
                m["prefix_tokens"].inc(s.matched_len)
        self._publish_page_gauges()
        s.admit_t = time.monotonic()
        self._slots[idx] = s
        return True

    def step(self) -> bool:
        """One scheduler iteration: admit, dispatch a fused
        decode+prefill block, then fetch the PREVIOUS block's tokens
        (ready by now — lag-1 pipelining). Returns True if any work
        ran."""
        with _acquired(self, self._lock):
            if not self._has_work_locked():
                return False
        with tracing.step_span("rt.llm.step", into=self.account,
                               slots=self.num_slots,
                               block=self.decode_block) as sp:
            self._steps += 1
            stages: Dict[str, float] = {}
            mark = self._read_clocks()
            ran, program, active, admitted = self._step(sp, stages)
            ends = wall, off_cpu, caller_cpu = self._since(mark)
            hole, under = 0.0, None
            if program != "none":
                typical = self._typical[program]
                usual = typical.mean or 0.0
                over = typical.over(wall / 1e9)
                if over >= HOLE_S:
                    hole = over
                    # what no stage covers (the fetched array's release,
                    # a collection between two stages) is the step's own
                    stages["step"] = wall / 1e9 - sum(stages.values())
                    under = self._keep_hole(over, usual, mark, ends, program,
                                            stages, active, admitted)
            if sp.recording:
                sp.set(wall_us=wall / 1e3, off_cpu_us=off_cpu / 1e3,
                       caller_cpu_us=caller_cpu / 1e3, hole_ms=hole * 1e3)
                if under:
                    sp.set(hole_stage=under)
            return ran

    # -- the loop's time account -------------------------------------------

    def _caller_cpu_ns(self, clock: Optional[int]) -> int:
        if clock is None:
            return 0
        try:
            return time.clock_gettime_ns(clock)
        except OSError:  # the thread is gone
            if clock == self._caller_clock:
                self._caller_clock = None
            return 0

    def _read_clocks(self) -> tuple:
        """A mark to measure from: the wall, this thread's CPU and the
        CPU of the thread that last called ``submit()`` (NOT the
        process's: summing a JAX process's hundreds of threads costs
        microseconds a read; 0 where there is no such thread or clock; the
        caller's own where the caller steps the engine itself), in ns,
        and the process's collections, compiles and this engine's
        callbacks so far."""
        events = tracing.process_events()
        clock = self._caller_clock
        return (time.perf_counter_ns(), time.thread_time_ns(),
                self._caller_cpu_ns(clock), clock, events.gc_pause_s,
                events.compiles, self._callbacks)

    def _since(self, mark: tuple) -> tuple:
        """``(wall, off_cpu, caller_cpu)`` in ns since ``mark``. Off the
        CPU while the caller's thread burned about as much is the
        interpreter lock; off the CPU with the caller asleep too is the
        device, the runtime or a lock a sleeper holds. The thread's clock
        is read inside the wall clock's interval, and neither difference
        is clamped: where the kernel charges CPU time a tick at a time,
        one reading is all of the wall or less than none (sums stay
        true)."""
        wall0, cpu0, caller0, clock = mark[:4]
        caller_cpu = 0
        if clock is not None and clock == self._caller_clock:
            caller_cpu = max(0, self._caller_cpu_ns(clock) - caller0)
        on_cpu = time.thread_time_ns() - cpu0
        wall = time.perf_counter_ns() - wall0
        return wall, wall - on_cpu, caller_cpu

    def _keep_hole(self, over_s: float, typical_s: float, mark: tuple,
                   ends: tuple, program: str, stages: Dict[str, float],
                   active: int = 0, admitted: int = 0) -> str:
        """Count a hole and keep it with what lay beside it from ``mark``
        to ``ends`` (``_read_clocks``, ``_since``): the stage it lay under
        (the longest of the step's own, ``step`` where that is what none
        of them covers; returned), what this thread and
        the caller's did meanwhile, the collections and compiles of the
        process. Takes no lock: an ``acquire`` hole is kept holding the
        engine's."""
        wall, off_cpu, caller_cpu = ends
        events = tracing.process_events()
        stage = max(stages, key=stages.get)
        self.holes += 1
        self.hole_s += over_s
        self._holes.append({
            "t_unix": time.time(), "step": self._steps, "program": program,
            "wall_ms": round(wall / 1e6, 3),
            "typical_ms": round(typical_s * 1e3, 3),
            "over_ms": round(over_s * 1e3, 3),
            "stages_ms": {k: round(v * 1e3, 3) for k, v in stages.items()},
            "stage": stage,
            "off_cpu_ms": round(off_cpu / 1e6, 3),
            "caller_cpu_ms": round(caller_cpu / 1e6, 3),
            "gc_ms": round((events.gc_pause_s - mark[4]) * 1e3, 3),
            "compiled": events.compiles - mark[5],
            "active": active, "admitted": admitted,
            "callbacks": self._callbacks - mark[6]})
        return stage

    def loop_account(self) -> dict:
        """The engine loop's time account since the engine was built:
        every stage's count, total and longest run, the holes counted and
        the last ``HOLES_KEPT`` of them. Read from any thread."""
        return {"stages": self.account.snapshot(), "holes": self.holes,
                "hole_s": round(self.hole_s, 6),
                "last_holes": list(self._holes)}

    def _step(self, sp, stages: Dict[str, float]) -> tuple:
        """``(ran, program, active, admitted)``; each stage's seconds
        into ``stages`` under its name."""
        ran_control = False
        with tracing.step_span("rt.llm.schedule",
                               into=self.account) as sched, \
                self._lock:
            # Session export/import and friends run HERE, between
            # decode steps: the previous block's cache assignment is
            # complete and the next dispatch hasn't consumed it.
            while self._control:
                self._control.popleft()()
                ran_control = True
            self._shed_expired_locked()
            admitted = 0
            for i in range(self.num_slots):
                if self._slots[i] is None and self._pending:
                    if not self._admit_locked(i, self._pending[0]):
                        break  # pool exhausted; FIFO order preserved
                    self._pending.popleft()
                    admitted += 1
            prefill_idx = next(
                (i for i, s in enumerate(self._slots)
                 if s is not None and not s.prefill_done), None)
            active = [(i, s) for i, s in enumerate(self._slots)
                      if s is not None and s.prefill_done
                      and not s.first_tok_pending]
            live = [s for s in self._slots if s is not None]
            pending = len(self._pending)
            sched.set(admitted=admitted)
        stages["schedule"] = sched.seconds
        ran = ran_control
        had_fetch = self._inflight is not None
        program, pre_tokens, waiting = "none", 0, len(live) - len(active)
        if prefill_idx is not None:
            program = "block"
            s = self._slots[prefill_idx]
            pre_tokens = min(self.chunk, len(s.prompt) - s.prefill_offset)
            self.steps_block += 1
        elif active:
            program = "decode_only"
            self.steps_decode_only += 1
        if program != "none":
            k = self.decode_block
            self.slot_steps += self.num_slots * k
            self.slot_steps_active += len(active) * k
            self.slot_steps_prefill_wait += waiting * k
            self.prefill_tokens += pre_tokens
            pages_read = self._pages_read(active, prefill_idx)
            self.kv_pages_read += pages_read
        if sp.recording:
            ps = self.page_size
            sp.set(program=program, active=len(active),
                   prefill_tokens=pre_tokens,
                   # the lane this step's program carried a prompt in
                   lane=self.chunk if program == "block" else 0,
                   prefill_waiting=waiting,
                   pending=pending, pages_allocated=self._pool.used_count,
                   pages_read=pages_read if program != "none" else 0,
                   # written so far: a prompt in the lane has pos 0 and
                   # prefill_offset tokens in its pages; pos runs past
                   # the reservation by the overshoot, which lands in
                   # the scratch page
                   pages_written=sum(
                       min(len(s.pages),
                           -(-max(s.pos, s.prefill_offset) // ps))
                       for s in live))
        new_block = None
        if program != "none":
            with tracing.step_span("rt.llm.dispatch", cpu=True,
                                   into=self.account):
                new_block = self._dispatch_block(active, prefill_idx,
                                                 stages)
        if had_fetch:
            self._process_fetch(sp, stages)
            ran = True
        if new_block is not None:
            self._inflight = new_block
            ran = True
        return ran, program, len(active), admitted

    def _pages_read(self, active, prefill_idx) -> int:
        """Pages the rows about to be dispatched attend over, a layer:
        a decode row at position p reads its p + 1 live positions, in
        each of the block's steps; the lane's slot reads its prompt up
        to the end of this chunk."""
        ps, cap = self.page_size, self.cfg.max_seq
        pages = sum(-(-(s.pos + k + 1) // ps)
                    for _, s in active for k in range(self.decode_block)
                    if s.pos + k < cap)
        if prefill_idx is not None:
            s = self._slots[prefill_idx]
            pages += -(-min(len(s.prompt),
                            s.prefill_offset + self.chunk) // ps)
        return pages

    def _dispatch_block(self, active, prefill_idx, stages):
        """Dispatch one K-step block: every active slot decodes K
        tokens and (when a slot is mid-prompt) ONE prefill chunk rides
        the first step's fused program: up to ``self.chunk`` tokens of
        one slot's prompt, the width the caller gave or
        :func:`prefill_lane` derived. Continuing slots chain their
        input token device-side; freshly prefilled slots inject theirs
        via the override vector."""
        # The fused program unless no prompt chunk is pending and the
        # family has the cheap pure-decode program. With no prompt
        # pending (a family whose rows repeat bit for bit only within ONE
        # compiled program, models/serving.py ``one_program``) the lane
        # is empty: n_valid 0 writes nothing, and what it samples nobody
        # reads.
        fused = prefill_idx is not None or self._model.one_program
        layout, idle = self._host_in[fused]
        with tracing.step_span("rt.llm.dispatch.pack",
                               into=self.account) as pack:
            host_in = idle.copy()
            f = layout.views(host_in)
            # the table as it stands NOW: the live one is written again
            # at the next admission or release
            f["tables"][:] = self._tables
            pos, temps, seeds = f["pos"], f["temps"], f["seeds"]
            override_vals, override_mask = (f["override_vals"],
                                            f["override_mask"])
            for i, s in active:
                pos[i] = s.pos
                temps[i] = s.temperature
                seeds[i] = s.seed
                if s.on_device_chain:
                    override_mask[i] = 0
                else:
                    override_vals[i] = s.last_token
            # Prefill lane: one chunk of one slot's prompt rides the
            # fused program's first step.
            pre_info = None
            if prefill_idx is not None:
                s = self._slots[prefill_idx]
                if s.prefill_start_t == 0.0:
                    s.prefill_start_t = time.monotonic()
                p0 = s.prefill_offset
                piece = s.prompt[p0:p0 + self.chunk]
                n_valid = len(piece)
                f["pre_tokens"][:n_valid] = piece
                s.prefill_offset = p0 + n_valid
                final = s.prefill_done
                if final:
                    s.first_tok_pending = True
                pre_info = (prefill_idx, s, final)
                f["lane_slot"][0], f["p0"][0] = prefill_idx, p0
                f["n_valid"][0], f["lane_seed"][0] = n_valid, s.seed
                f["lane_temp"][0] = s.temperature
        with tracing.step_span("rt.llm.dispatch.upload", cpu=True,
                               into=self.account) as upload:
            # ONE transfer a dispatch; _last_dev never left the device
            host_dev = jnp.asarray(host_in)
            if upload.recording:
                upload.set(arrays=1, bytes=host_in.nbytes)
        with tracing.step_span("rt.llm.dispatch.launch",
                               into=self.account) as sp:
            built = tracing.process_events().compiles
            pre_tok = None
            step = self._block if fused else self._decode_only
            out = step(self._params, self._cache, self._last_dev, host_dev)
            if fused:
                toks_k, self._last_dev, pre_tok, self._cache = out
            else:
                toks_k, self._last_dev, self._cache = out
            if sp.recording:
                # the executable called, and the programs built for the
                # backend meanwhile: 0 in a loop that was warmed up
                sp.set(program="block" if fused else "decode_only",
                       compiled=tracing.process_events().compiles - built)
        stages["pack"], stages["upload"] = pack.seconds, upload.seconds
        stages["launch"] = sp.seconds
        for i, s in active:
            s.pos += self.decode_block
            s.on_device_chain = True
        return (list(active), pre_info, toks_k, pre_tok)

    def _process_fetch(self, step_sp, stages) -> None:
        snapshot, pre_info, toks_k, pre_tok = self._inflight
        self._inflight = None
        with tracing.step_span("rt.llm.fetch", into=self.account) as sp:
            # the lag-1 wait for the device: the block dispatched one
            # step ago is usually ready, so this is a fast fetch
            arr = np.asarray(toks_k)  # [K, rows (+ the family's counts)]
        stages["fetch"] = sp.seconds
        names = self._model.step_counters
        if names:
            # the counts of the block dispatched one step ago, on the
            # span of the step that fetched them
            counts = arr[:, self.num_slots:].sum(axis=0)
            arr = arr[:, :self.num_slots]
            for name, n in zip(names, counts):
                setattr(self, name, getattr(self, name) + int(n))
            if step_sp.recording:
                step_sp.set(**{k: int(n) for k, n in zip(names, counts)})
        with tracing.step_span("rt.llm.deliver", cpu=True,
                               into=self.account) as sp:
            tokens0, done0 = self.tokens_generated, self.requests_completed
            calls0 = self._callbacks
            # the metric family once a fetch, its token counter once a
            # deliver: nobody reads either a token at a time
            metrics = llm_metrics()
            overshoot = self._deliver_block(snapshot, pre_info, arr,
                                            pre_tok, metrics)
            self.overshoot_tokens += overshoot
            delivered = self.tokens_generated - tokens0
            if metrics is not None and delivered:
                metrics["tokens"].inc(float(delivered))
            sp.set(delivered=delivered,
                   finished=self.requests_completed - done0,
                   overshoot=overshoot,
                   # on_token calls: each wakes the caller's thread
                   callbacks=self._callbacks - calls0)
        stages["deliver"] = sp.seconds

    def _deliver_block(self, snapshot, pre_info, arr, pre_tok,
                       metrics) -> int:
        """Hand a fetched block's tokens to their requests. Returns the
        tokens the device computed and nobody gets: the rest of a block
        after EOS / length, and the whole block of a slot that finished
        while it was in flight."""
        k_block = arr.shape[0]
        overshoot = 0
        for idx, s in snapshot:
            if self._slots[idx] is not s:
                overshoot += k_block  # finished in an earlier block
                continue
            for k in range(k_block):
                self._deliver(idx, s, int(arr[k, idx]), metrics)
                if self._slots[idx] is not s:
                    overshoot += k_block - 1 - k  # eos / length mid-block
                    break
        if pre_info is not None:
            idx, s, final = pre_info
            if final and self._slots[idx] is s:
                # Prefill complete: file the prompt's fully-covered
                # pages in the radix index NOW (not at request end), so
                # a concurrent same-prefix admission already hits them.
                if self._radix is not None and not s.inserted:
                    with self._lock:
                        self._radix.insert(
                            s.prompt, s.pages[:len(s.prompt)
                                              // self.page_size])
                    s.inserted = True
                # The prompt's sampled first token arrives with the
                # block fetch; the slot joins the decode batch next
                # dispatch (override lane — the token is host-side).
                s.first_tok_pending = False
                s.pos = len(s.prompt)
                s.on_device_chain = False
                self._deliver(idx, s, int(pre_tok), metrics)
        return overshoot

    def _request_timing(self, s: _Slot) -> dict:
        """Stage decomposition of one finished request. admission =
        waiting in the pending FIFO for a slot + pages; queue = admitted
        but not yet in the prefill lane; prefill = first chunk dispatch
        to first token; decode = the rest. Sums to ~total by
        construction (clamps only absorb clock jitter)."""
        end = time.monotonic()
        admit = s.admit_t or s.submit_t
        pre0 = s.prefill_start_t or admit
        first = s.first_tok_t or end
        timing = {
            "admission_s": max(0.0, admit - s.submit_t),
            "queue_s": max(0.0, pre0 - admit),
            "prefix_match_s": s.prefix_match_s,
            "prefill_s": max(0.0, first - pre0),
            "decode_s": max(0.0, end - first),
            "decode_per_token_s": (max(0.0, end - first)
                                   / max(1, s.produced - 1)),
            "total_s": max(0.0, end - s.submit_t),
            "matched_tokens": s.matched_len,
            "produced_tokens": s.produced,
        }
        m = llm_metrics()
        if m is not None:
            st = m["stage"]
            st.observe_key(_LLM_STAGE_KEYS["admission"],
                           timing["admission_s"])
            st.observe_key(_LLM_STAGE_KEYS["queue"], timing["queue_s"])
            st.observe_key(_LLM_STAGE_KEYS["prefix_match"],
                           timing["prefix_match_s"])
            st.observe_key(_LLM_STAGE_KEYS["prefill"],
                           timing["prefill_s"])
            st.observe_key(_LLM_STAGE_KEYS["decode"], timing["decode_s"])
            m["decode_per_token"].observe(timing["decode_per_token_s"])
        return timing

    def _emit_trace_spans(self, s: _Slot, timing: dict) -> None:
        """Turn the finished request's `timing` stage breakdown into
        child spans on its propagated trace: an ``llm.request`` span
        parented to the serve request, with admission/queue/prefix_match/
        prefill/decode children laid out from the SAME durations the
        timing dict reports (so span tree and `timing` metadata agree by
        construction). Stamps are monotonic; the wall offset lines them
        up with proxy/replica spans within clock-sampling noise.

        What these are: host wall-clock STAGES of one request, laid end
        to end from its stamps, for ``rt trace``. They time no device
        work: a decode stage is the wall time the request spent in the
        batch, shared with every other slot's. The device's time is in
        the profiler trace, under the programs' scope names, and what
        each engine step was made of is in the ``rt.llm.*`` step spans."""
        if not tracing.get_tracer().enabled:
            return
        off = time.time() - time.monotonic()
        t0 = s.submit_t + off
        trace_id, parent = s.trace_ctx
        root = tracing.record_span(
            "llm.request", trace_id=trace_id, parent_id=parent,
            start_s=t0, end_s=t0 + timing["total_s"],
            prompt_len=int(len(s.prompt)), produced=int(s.produced),
            matched_tokens=int(s.matched_len))
        if root is None:
            return
        cur = t0
        for stage in ("admission", "queue", "prefill", "decode"):
            dur = timing[f"{stage}_s"]
            tracing.record_span(f"llm.{stage}", trace_id=trace_id,
                                parent_id=root.span_id, start_s=cur,
                                end_s=cur + dur)
            cur += dur
        if timing["prefix_match_s"] > 0.0:
            # Overlaps the queue->prefill boundary (the match runs at
            # admission into the prefill lane); rendered as its own
            # child rather than folded into either stage.
            match_t0 = t0 + timing["admission_s"] + timing["queue_s"]
            tracing.record_span("llm.prefix_match", trace_id=trace_id,
                                parent_id=root.span_id, start_s=match_t0,
                                end_s=match_t0 + timing["prefix_match_s"])

    def request_timings(self, since_unix_s: float = 0.0) -> List[dict]:
        """The ``timing`` of the last ``TIMINGS_KEPT`` finished requests
        submitted at or after ``since_unix_s``, oldest first, each with
        its ``seed``, ``submit_unix_s`` and, where the request came with
        a trace context, its ``request_id`` (the x-request-id)."""
        with self._lock:
            kept = list(self._timings)
        return [t for t in kept if t["submit_unix_s"] >= since_unix_s]

    def _deliver(self, idx: int, s: _Slot, tok: int, metrics) -> None:
        s.last_token = tok
        s.produced += 1
        self.tokens_generated += 1
        if s.produced == 1:
            s.first_tok_t = time.monotonic()
            if metrics is not None:
                metrics["ttft"].observe(s.first_tok_t - s.submit_t)
        s.handle._emit(tok)
        if s.on_token:
            self._callbacks += 1
            s.on_token(tok)
        hit_eos = s.eos_id is not None and tok == s.eos_id
        out_of_room = (len(s.prompt) + s.produced) >= self.cfg.max_seq
        if hit_eos or s.produced >= s.max_new or out_of_room:
            s.handle.timing = self._request_timing(s)
            kept = dict(s.handle.timing, seed=s.seed, submit_unix_s=(
                s.submit_t + time.time() - time.monotonic()))
            if s.trace_ctx is not None:
                kept["request_id"] = s.trace_ctx[0]
                self._emit_trace_spans(s, s.handle.timing)
            with self._lock:
                self._timings.append(kept)
            # counted BEFORE anyone is told: the two calls below wake the
            # caller's thread, and a caller that then reads the counters
            # (stats()) must find its request among the completed, however
            # long this thread then waits for the interpreter lock
            self.requests_completed += 1
            s.handle._finish("stop" if hit_eos else "length")
            if s.on_token:
                self._callbacks += 1
                s.on_token(None)
            with self._lock:
                if s.session_id is not None:
                    # Transcript = prompt + everything produced: the
                    # session's next turn (or its migration target)
                    # reconstructs from exactly this token list.
                    self._record_session_locked(
                        s.session_id,
                        np.concatenate([
                            s.prompt,
                            np.asarray(s.handle._tokens, np.int32)]),
                        s.seed, s.temperature)
                self._release_slot_pages_locked(s)
                self._tables[idx] = 0
                self._slots[idx] = None
                self._publish_page_gauges()
