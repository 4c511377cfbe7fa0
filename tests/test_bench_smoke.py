"""bench.py --smoke: the bench scenarios can't bitrot between rounds.

Runs the real bench entrypoint in a subprocess (it owns its runtime and
serve instance) with BENCH_SMOKE_FAST=1 — tiny windows, every scenario
code path: core microbench (paired actor-vs-task + put-vs-memcpy ratios,
copy counts) and the mixed HTTP + direct-handle + streaming stage with
p50/p99 latency output.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_runs_all_stages():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["BENCH_SMOKE_FAST"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=280, env=env, cwd=REPO)
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("RESULT::")), None)
    assert line is not None, (
        f"no RESULT:: line rc={proc.returncode}\n"
        f"stdout: {proc.stdout[-800:]}\nstderr: {proc.stderr[-800:]}")
    result = json.loads(line[len("RESULT::"):])

    assert "core_microbench_error" not in result, result
    micro = result["core_microbench"]
    # The acceptance-criteria keys must exist and be sane.
    assert micro["1_1_actor_calls_sync"] > 0
    assert micro["single_client_tasks_sync"] > 0
    assert micro["actor_vs_task_sync"] > 0
    assert 0 < micro["put_large_(10MB)_vs_memcpy"] <= 2.0
    # Copy-count profile: a 10MB put is exactly ONE frame write and a
    # get is zero copies (zero-copy views out of the arena).
    assert micro["put_large_(10MB)_copies_per_op"] == 1.0
    assert micro["put_large_(10MB)_flatten_copies_per_op"] == 0.0
    assert micro["get_large_(10MB)_copies_per_op"] == 0

    assert "serve_mixed_error" not in result, result
    mixed = result["serve_mixed"]
    assert "errors" not in mixed, mixed
    # Every traffic class moved AND reported tail latency.
    for klass in ("http", "handle"):
        assert mixed[f"{klass}_reqs_per_s"] > 0, mixed
        assert mixed[f"{klass}_p50_ms"] > 0, mixed
        assert mixed[f"{klass}_p99_ms"] >= mixed[f"{klass}_p50_ms"], mixed
    assert mixed["stream_tokens_per_s"] > 0, mixed
    assert mixed["stream_first_chunk_p99_ms"] >= \
        mixed["stream_first_chunk_p50_ms"]

    # Serve chaos stage (ISSUE 18): a replica SIGKILLed under live
    # traffic — every request must end success / typed 503 / typed
    # deadline (zero hangs, zero raw 500s) and the controller must
    # replace the corpse, committing the replacement latency.
    assert "serve_chaos_error" not in result, result
    chaos = result["serve_chaos"]
    assert chaos["kills"] >= 1, chaos
    counts = chaos["counts"]
    assert counts["hung"] == 0, chaos
    assert counts["raw_500"] == 0, chaos
    assert counts["other"] == 0, chaos
    assert counts["ok"] > 0, chaos
    assert chaos["replaced_ms_p50"] > 0, chaos
    assert chaos["replaced_ms_p99"] >= chaos["replaced_ms_p50"], chaos
    assert chaos["during_kill_p99_ms"] >= 0, chaos

    # Telemetry plane wired through the bench: the mid-bench /metrics
    # scrape must see runtime counters AND worker/replica-shipped series
    # (latency histograms travel worker -> head over the pipe).
    assert "telemetry_scrape_error" not in result, result
    scrape = result["telemetry_scrape"]
    assert scrape["rt_tasks_submitted_total"] > 0, scrape
    assert scrape["rt_tasks_finished_total"] > 0, scrape
    assert scrape["rt_task_latency_seconds_count"] > 0, scrape
    assert scrape["rt_workers_alive"] > 0, scrape
    assert scrape["rt_serve_requests_total"] > 0, scrape
    assert scrape["rt_serve_request_latency_count"] > 0, scrape

    # Paged-KV multi-turn sessions (ISSUE 15): warm turns must hit the
    # radix prefix cache and prefill fewer tokens than cold ones. The
    # smoke gate holds the stage to what a count shows, whatever the
    # host's load; the warm/cold TTFT ratio (>= 2x) is the full bench's
    # to commit.
    assert "llm_sessions_error" not in result, result
    sess = result["llm_sessions"]
    assert sess["prefix_hit_rate"] > 0, sess
    assert sess["ttft_cold_ms_p50"] > 0 and sess["ttft_warm_ms_p50"] > 0
    assert sess["prefix_tokens_saved_warm"] > 0, sess
    assert sess["prefix_tokens_saved_cold"] == 0, sess
    assert sess["prefix_tokens_saved"] == sess["prefix_tokens_saved_warm"]

    # Stateful-session chaos stage (ISSUE 19): drain mid-traffic AND
    # SIGKILL mid-generation — sessions migrate (KV page export/import)
    # or recover (transcript re-prefill), continuations stay bit-for-bit
    # (greedy AND seeded), and no request is dropped: zero raw 500s,
    # zero hangs, zero drain-caused 503s.
    assert "llm_drain_error" not in result, result
    ld = result["llm_drain"]
    assert ld["drain"]["error"] is None, ld
    assert ld["drain"]["sessions_migrated"] >= 1, ld
    assert ld["drain"]["migrate_errors"] == 0, ld
    assert ld["drain"]["timed_out"] is False, ld
    assert ld["kills"] >= 1, ld
    dcounts = ld["counts"]
    assert dcounts["raw_500"] == 0, ld
    assert dcounts["hung"] == 0, ld
    assert dcounts["other"] == 0, ld
    assert dcounts["ok"] > 0, ld
    assert ld["drain_503"] == 0, ld
    assert ld["parity_greedy"] is True, ld
    assert ld["parity_seeded"] is True, ld
    assert ld["migrate_ms_p50"] > 0, ld
    assert ld["migrate_ms_p99"] >= ld["migrate_ms_p50"], ld
    assert ld["recovery_samples"] >= 1, ld
    assert ld["recovery_ms_p50"] > 0, ld

    # Long-gen decode stage (ISSUE 17): sustained decode tok/s with the
    # decode block committed next to the engine's own step counters,
    # plus the tp2 parity sub-stage — under the test env's
    # virtual devices it must run and hold bit-for-bit (a single-device
    # host skips it cleanly instead).
    assert "llm_longgen_error" not in result, result
    lg = result["llm_longgen"]
    assert lg["tokens_per_s_longgen"] > 0, lg
    assert lg["decode_block"] >= 1, lg
    assert lg["decode_steps"] > 0, lg
    # every slot decodes through the whole phase, and a request's last
    # in-flight block is computed for nobody (lag-1 dispatch)
    assert 0 < lg["slot_steps_active"] <= (
        lg["decode_steps"] * lg["concurrent_slots"]), lg
    assert lg["overshoot_tokens"] >= lg["concurrent_slots"], lg
    if isinstance(lg.get("tp2"), str):
        assert lg["tp2"].startswith("skipped"), lg
    else:
        assert lg["tp2_token_parity"] is True, lg
        assert "tp" in lg["tp2_kv_spec"], lg

    # Flight-recorder stage (ISSUE 16): per-stage task latency joined
    # head-side with worker exec deltas, stage sums ~= end-to-end, and
    # the LLM half commits per-request timing + the engine's step
    # counters.
    assert "bench_flight_error" not in result, result
    fl = result["bench_flight"]
    assert "task_join_timeout" not in fl, fl
    assert fl["task_rows_joined"] > 0, fl
    for stage in ("queue", "sched", "exec", "transfer", "total"):
        assert fl[f"task_{stage}_ms_p50"] >= 0, fl
        assert fl[f"task_{stage}_ms_p99"] >= fl[f"task_{stage}_ms_p50"], fl
    assert fl["task_exec_ms_p50"] > 0, fl
    # By construction queue+sched+exec+transfer == total; the 10%
    # acceptance tolerance leaves room for clamping on degenerate rows.
    assert abs(fl["task_stage_sum_frac_mean"] - 1.0) <= 0.1, fl
    assert fl["llm_requests"] > 0, fl
    for key in ("llm_prefill_ms_p50", "llm_decode_ms_p50",
                "llm_total_ms_p50"):
        assert fl[key] > 0, fl
    assert fl["llm_decode_steps"] > 0, fl
    assert fl["llm_slot_steps_active"] > 0, fl
    assert scrape["rt_task_stage_seconds_count"] > 0, scrape
    assert scrape["rt_llm_stage_seconds_count"] > 0, scrape

    # Head-failover recovery stage: subprocess heads on a shared WAL —
    # the chaos loop must actually kill and recover, committing latency.
    # (The stage degrades gracefully on toolchain-less hosts, matching
    # the build_native() skips of the dedicated failover tests.)
    assert "head_failover_error" not in result, result
    hf = result["head_failover"]
    if hf.get("error") != "native toolchain unavailable":
        assert "error" not in hf, hf
        assert hf["kills"] >= 1, hf
        assert hf["recoveries"] >= 1, hf
        assert hf["actors_restarted_total"] >= 1, hf
        assert hf["recover_ms_p50"] > 0, hf
        assert hf["recover_ms_p99"] >= hf["recover_ms_p50"], hf

    # Tracing-overhead A/B stage (ISSUE 20): paired traced/untraced
    # child runs must both execute, the traced child must actually
    # record spans, and the committed overhead figure must stay sane.
    # The 5% budget is enforced against the FULL bench run (see
    # BASELINE.md); smoke windows are short enough that scheduler noise
    # dominates, so the smoke gate is deliberately loose.
    assert "tracing_overhead_error" not in result, result
    to = result["tracing_overhead"]
    assert "error" not in to, to
    assert to["tasks_per_s_traced"] > 0, to
    assert to["tasks_per_s_untraced"] > 0, to
    assert to["spans_traced"] > 0, to
    assert to["spans_untraced"] == 0, to
    assert len(to["pair_ratios"]) >= 2, to
    assert to["overhead_frac"] <= 0.35, to
