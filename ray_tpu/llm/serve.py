"""Serve deployment hosting a :class:`SlotEngine` — the on-TPU LLM
serving path.

A replica owns one compiled model + KV-slot pool; HTTP requests join
free slots mid-flight and stream tokens back over the proxy's chunked
path. Request schema (POST body JSON):

    {"prompt": [token ids...], "max_tokens": 64, "temperature": 0.0,
     "eos_id": null, "stream": false}

Responses: ``{"tokens": [...], "finish_reason": ..., "prompt_len": N,
"timing": {...}}`` — ``timing`` is the flight recorder's per-request
stage breakdown (admission/queue/prefix_match/prefill/decode seconds) —
or, with ``stream: true``, one JSON token-id per chunk line.

Reference analog: ``/root/reference/python/ray/serve/_private/replica.py``
(replica request plane) — then beyond it: the reference has no
accelerator-resident serving loop at all.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

import jax

from ..models import serving
from ..observability import tracing
from .engine import SlotEngine
from .paged import OverloadedError


def _build_params(model: str, seed: int,
                  checkpoint_path: Optional[str] = None):
    cfg, family = serving.named(model)
    if checkpoint_path:
        from ..train.checkpoint import restore_arrays

        params = restore_arrays(checkpoint_path)
    else:
        params, _ = family.init_params(jax.random.PRNGKey(seed), cfg)
    if cfg.dtype is not None:
        params = jax.tree.map(lambda x: x.astype(cfg.dtype), params)
    return params, cfg


class LLMServer:
    """Deployment class: one engine per replica, asyncio request plane.

    The engine thread drives the TPU; handlers only bridge tokens into
    the replica's event loop, so hundreds of concurrent streams cost one
    queue hop each, never a device touch.
    """

    def __init__(self, model: str = "llama-tiny", num_slots: int = 8,
                 chunk: Optional[int] = None, seed: int = 0,
                 checkpoint_path: Optional[str] = None,
                 default_max_tokens: int = 64,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefix_cache: bool = True,
                 max_pending: Optional[int] = 256,
                 queue_timeout_s: Optional[float] = 30.0,
                 decode_block: int = 1, tp: int = 1):
        t0 = time.monotonic()
        tracing.watch_compiles()  # set-up's own compiles are counted too
        built = tracing.process_events().counters()
        params, cfg = _build_params(model, seed, checkpoint_path)
        t_params = time.monotonic()
        self.default_max_tokens = default_max_tokens
        # tp > 1: tensor-shard this replica over the first tp local
        # devices — params by their logical axes, KV pages on the
        # kv-heads axis (SlotEngine.SERVE_RULES). Per-request fold_in
        # sampling keeps outputs bit-for-bit identical to tp=1.
        mesh = None
        if tp > 1:
            from ..parallel.mesh import MeshSpec

            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tp={tp} needs {tp} devices, have {len(devs)}")
            mesh = MeshSpec(tp=tp).build(devs[:tp])
        # Per-deployment admission control: the pending queue is BOUNDED
        # (max_pending) and queued requests expire after queue_timeout_s
        # — both shed load as a typed OverloadedError that the HTTP
        # proxy maps to 503, instead of letting a traffic wave grow
        # engine._pending without limit and stall resident sessions.
        self.engine = SlotEngine(params, cfg, num_slots=num_slots,
                                 chunk=chunk, seed=seed,
                                 page_size=page_size, num_pages=num_pages,
                                 prefix_cache=prefix_cache,
                                 max_pending=max_pending,
                                 queue_timeout_s=queue_timeout_s,
                                 decode_block=decode_block, mesh=mesh)
        t_engine = time.monotonic()
        self.engine.warmup()  # compile before the replica is routable
        # Set-up seconds, apart from any request's: building the weights,
        # the engine (placement + page pool), and the warm-up request
        # that compiles both programs. Of all three, compile_s went on
        # building programs for the backend, compile_cache_hits of them
        # read back from the persistent cache: a cold start against a
        # warm one, said by the program.
        now = tracing.process_events().counters()
        self._startup_s = {
            "params": round(t_params - t0, 3),
            "engine": round(t_engine - t_params, 3),
            "warmup": round(time.monotonic() - t_engine, 3),
            "compile_s": round(now["compile_s"] - built["compile_s"], 3),
            "compile_cache_hits": (now["compile_cache_hits"]
                                   - built["compile_cache_hits"])}
        self.engine.start()
        self._recoveries: list = []  # crash-path restore latencies (ms)

    def __del__(self):
        try:
            self.engine.stop()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    async def __call__(self, payload):
        if not isinstance(payload, dict) or "prompt" not in payload:
            return {"error": "body must be JSON with a 'prompt' "
                             "token-id list"}
        prompt = payload["prompt"]
        max_tokens = int(payload.get("max_tokens",
                                     self.default_max_tokens))
        temperature = float(payload.get("temperature", 0.0))
        eos_id = payload.get("eos_id")
        # Client-pinned seed: a safe retry after replica death replays
        # the identical request elsewhere; with the seed in the payload
        # the fold_in sampling stream — and therefore the output — is
        # bit-for-bit the same on the survivor.
        seed = payload.get("seed")
        session_id = payload.get("session")
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        handle = self.engine.submit(
            prompt, max_new=max_tokens, temperature=temperature,
            eos_id=None if eos_id is None else int(eos_id),
            seed=None if seed is None else int(seed),
            session_id=None if session_id is None else str(session_id),
            on_token=lambda t: loop.call_soon_threadsafe(q.put_nowait, t),
            # The replica bound the request's trace ctx to THIS asyncio
            # task (handle_request); hand it to the engine thread so the
            # stage spans it synthesizes at finish join the same trace.
            trace_ctx=tracing.get_request_context())
        if payload.get("stream"):
            # Hold the response until the FIRST token (or failure): the
            # proxy writes the chunked 200 header as soon as it sees a
            # stream, so an admission shed surfacing after that point
            # could only be reported as a dropped connection. Raising
            # here instead lets the proxy send the typed 503. TTFB was
            # going to be the first token anyway.
            first = await q.get()
            if first is None and handle.error is not None:
                raise handle.error

            async def token_stream():
                tok = first
                while tok is not None:
                    yield tok
                    tok = await q.get()
                if handle.error is not None:
                    raise handle.error

            return token_stream()
        while True:
            if await q.get() is None:
                break
        if handle.error is not None:
            raise handle.error
        res = handle.result(timeout=0)
        # "timing": the flight recorder's per-request stage breakdown
        # (admission/queue/prefix_match/prefill/decode seconds) — every
        # response carries its own latency attribution.
        return {"tokens": res.tokens, "finish_reason": res.finish_reason,
                "prompt_len": res.prompt_len, "timing": res.timing}

    # -- stateful sessions (migration & drain, ISSUE 19) -------------------

    def sessions(self) -> list:
        """Resident session ids on this replica's engine."""
        return self.engine.sessions()

    def export_sessions(self, session_ids=None) -> list:
        """Snapshot sessions for migration (controller drain path).
        Skips ids with a generation currently in flight — the drain
        quiesce wait retries nothing; those sessions recover via the
        crash path's re-prefill if they move."""
        ids = session_ids if session_ids else self.engine.sessions()
        out = []
        for sid in ids:
            try:
                out.append(self.engine.export_session(sid))
            except (KeyError, RuntimeError):
                continue
        return out

    def import_session(self, snapshot) -> dict:
        return self.engine.import_session(snapshot)

    def restore_session(self, session_id, transcript, seed=None,
                        temperature: float = 0.0) -> dict:
        """Crash-path recovery: re-prefill the transcript (proxy calls
        this on re-pin when the old replica died without exporting)."""
        info = self.engine.prefill_session(session_id, transcript,
                                           seed=seed,
                                           temperature=temperature)
        self._recoveries.append(round(info["seconds"] * 1e3, 3))
        del self._recoveries[:-64]
        return info

    def stats(self) -> dict:
        from ..parallel.mesh import device_triple

        return {
            # What this replica runs on, as JAX reports it HERE: the
            # replica holds the chip, so a driver asks it, not JAX.
            "device": device_triple(),
            "startup_s": dict(self._startup_s),
            "tokens_generated": self.engine.tokens_generated,
            "requests_completed": self.engine.requests_completed,
            "requests_shed": self.engine.requests_shed,
            "num_slots": self.engine.num_slots,
            "prefix_hits": self.engine.prefix_hits,
            "prefix_misses": self.engine.prefix_misses,
            "prefix_tokens_saved": self.engine.prefix_tokens_saved,
            "pages_used": self.engine.pages_used,
            "pages_free": self.engine.pages_free,
            "sessions_resident": self.engine.session_count,
            "session_recovery_ms": list(self._recoveries),
            # what the engine's steps were made of, cumulative (the
            # rt.llm.step span carries the same counts per step)
            **{k: getattr(self.engine, k)
               for k in SlotEngine.STEP_COUNTERS},
            # the prefill lane's width, and the share of it that held
            # prompt tokens in the steps that carried a prompt
            "prefill_lane": self.engine.chunk,
            "prefill_lane_fill": self.engine.prefill_lane_fill,
            # what stopped this process's loops from outside them:
            # garbage collections, and programs built for the backend
            # (after warm-up there should be none)
            **tracing.process_events().counters(),
            # the engine loop's own time account, always on: every
            # stage's count, total and longest run, and the steps that ran
            # 20 ms or more over their kind's typical (engine.HOLE_S)
            "loop": self.engine.loop_account(),
        }

    def request_timings(self, since_unix_s: float = 0.0) -> list:
        """Stage timing of recently finished requests, streamed ones
        included (a stream carries tokens only): see
        ``SlotEngine.request_timings``."""
        return self.engine.request_timings(since_unix_s)


def build_llm_app(model: str = "llama-tiny", num_slots: int = 8,
                  chunk: Optional[int] = None, seed: int = 0,
                  checkpoint_path: Optional[str] = None,
                  name: str = "llm", page_size: int = 16,
                  num_pages: Optional[int] = None,
                  prefix_cache: bool = True,
                  max_pending: Optional[int] = 256,
                  queue_timeout_s: Optional[float] = 30.0,
                  decode_block: int = 1, tp: int = 1,
                  **deploy_opts):
    """Build a Serve application for ``serve.run`` hosting the engine.

    ``chunk`` is the prefill lane's width in prompt tokens a step. Left
    ``None``, the engine derives it once, in the replica that holds the
    chip (``llm/engine.py prefill_lane``): the chip's ridge — the rows a
    step's one read of the weights multiplies for nothing, 256 in
    bfloat16 on a TPU v5e — for a family that pays the lane only while a
    prompt is pending, and 64 for one that carries it on every step
    (``models/serving.py one_program``) or on a device with no published
    peaks (``parallel/mesh.py DEVICE_PEAKS``)."""
    from ..serve import deployment

    # Mirror the engine's admission knobs into the deployment config so
    # the router sheds at the same bound BEFORE a request crosses into
    # the replica (the engine's own bounded queue stays authoritative
    # for in-replica admission).
    deploy_opts.setdefault("max_pending", max_pending)
    deploy_opts.setdefault("queue_timeout_s", queue_timeout_s)
    # A replica that holds an accelerator can be deaf for tens of seconds
    # and healthy: a device-runtime call that keeps the interpreter lock
    # (stopping a profiler trace costs ~40 us a device event, 20-40 s for
    # five seconds of a fast decode loop: PERF.md, PR 25) answers no
    # probe. Serve's default, three probes of 5 s, kills it there, loses
    # the warm engine, and the replacement cannot take the chip while
    # the old process still has it. Three probes of 30 s.
    deploy_opts.setdefault("health_check_timeout_s", 30.0)
    dep = deployment(LLMServer, name=name, **deploy_opts)
    return dep.bind(model=model, num_slots=num_slots, chunk=chunk,
                    seed=seed, checkpoint_path=checkpoint_path,
                    page_size=page_size, num_pages=num_pages,
                    prefix_cache=prefix_cache, max_pending=max_pending,
                    queue_timeout_s=queue_timeout_s,
                    decode_block=decode_block, tp=tp)
