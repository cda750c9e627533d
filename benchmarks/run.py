"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the
wall-clock microseconds per protocol query (or per kernel call);
``derived`` carries the figure-of-merit the paper's table reports
(accuracy, $/query, reduction factor, ...).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--tasks N]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

from repro.core import (CostModel, MinionConfig, MinionSConfig, Usage,
                        run_local_only, run_minion, run_minions, run_rag,
                        run_remote_only)
from repro.core.latency import (H100_NODE, LLAMA_405B, LLAMA_8B, RTX_4090,
                                minions_latency_ratio, prop_c1_bound)
from repro.core.simulated import (ScriptedRemote, SimulatedLocal,
                                  context_factor, steps_factor)
from repro.core.tasks import make_dataset, score_answer
from repro.launch import compile_cache

CM = CostModel()
ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row)


def _evaluate(runner, tasks):
    t0 = time.time()
    correct, usage = 0, Usage()
    for t in tasks:
        r = runner(t)
        correct += score_answer(r.answer, t.answer)
        usage += r.remote_usage
    dt = (time.time() - t0) / len(tasks)
    return correct / len(tasks), CM.usd(usage) / len(tasks), dt * 1e6


# ===========================================================================
# Table 1 / Figure 2: cost-accuracy of all protocols and baselines
# ===========================================================================


def table1_cost_accuracy(n_tasks: int):
    tasks = make_dataset(n_tasks, seed=7, n_pages=120)  # ~30k-token docs
    remote = ScriptedRemote(seed=0)
    acc_r, cost_r, us = _evaluate(
        lambda t: run_remote_only(remote, t.context, t.query), tasks)
    emit("table1/remote_only", us, f"acc={acc_r:.3f};usd={cost_r:.4f}")
    for prof in ("llama-8b", "llama-3b", "llama-1b"):
        local = SimulatedLocal(prof, seed=0)
        acc, cost, us = _evaluate(
            lambda t: run_local_only(local, t.context, t.query), tasks)
        emit(f"table1/local_only_{prof}", us, f"acc={acc:.3f};usd=0")
        acc, cost, us = _evaluate(
            lambda t: run_minion(local, remote, t.context, t.query,
                                 MinionConfig(max_rounds=3)), tasks)
        emit(f"table1/minion_{prof}", us,
             f"acc={acc:.3f};usd={cost:.4f};reduction="
             f"{cost_r / max(cost, 1e-9):.1f}x;recovery={acc / acc_r:.1%}")
        acc, cost, us = _evaluate(
            lambda t: run_minions(local, remote, t.context, t.query,
                                  MinionSConfig()), tasks)
        emit(f"table1/minions_{prof}", us,
             f"acc={acc:.3f};usd={cost:.4f};reduction="
             f"{cost_r / max(cost, 1e-9):.1f}x;recovery={acc / acc_r:.1%}")


# ===========================================================================
# Figure 3 / Tables 4-5: small-LM limitation micro-experiments
# ===========================================================================


def fig3_context_length(n_tasks: int):
    """Accuracy of a 3B worker on a single extraction instruction as the
    context grows (paper Table 4: 1 -> 128 chunks of 512 tokens)."""
    from repro.core.prompts import render_worker
    from repro.core.types import JobManifest, JobOutput
    from repro.core.tasks import make_task
    from repro.core.simulated import find_facts
    local = SimulatedLocal("llama-3b", seed=0)
    for n_chunks in (1, 16, 32, 64, 128):
        t0 = time.time()
        hits = trials = 0
        for seed in range(n_tasks * 2):
            t = make_task(seed, n_pages=max(1, n_chunks), kind="extract")
            chars = 2048 * n_chunks
            ctx = t.context[:chars]
            key = (t.needed[0].metric, t.needed[0].year)
            if key not in find_facts(ctx):
                continue
            job = JobManifest("0", 0, ctx,
                              f"Extract the value of the {key[0]} for "
                              f"fiscal year {key[1]}. Abstain if it is "
                              f"not present in this chunk.")
            local.seed = seed
            out = JobOutput.from_json_text(
                local.complete(render_worker(job)))
            hits += bool(out.answer
                         and f"{t.needed[0].value:.1f}" in out.answer)
            trials += 1
        us = (time.time() - t0) / max(trials, 1) * 1e6
        emit(f"fig3/context_{n_chunks}chunks", us,
             f"acc={hits / max(trials, 1):.3f};rel="
             f"{context_factor(512 * n_chunks):.3f}")


def fig3_multistep(n_tasks: int):
    """Accuracy vs number of sub-tasks in one instruction (paper Table 5)."""
    paper = {1: 0.703, 2: 0.398, 3: 0.195, 4: 0.148}
    for k in (1, 2, 3, 4):
        emit(f"fig3/substeps_{k}", 0.0,
             f"rel={steps_factor(k):.3f};paper_abs={paper[k]}")


# ===========================================================================
# Figure 5: scaling parallel workloads on-device
# ===========================================================================


def fig5_parallel_scaling(n_tasks: int):
    tasks = make_dataset(n_tasks, seed=21, n_pages=60)
    remote = ScriptedRemote(seed=0)
    local = SimulatedLocal("llama-3b", seed=0)
    for n_tasks_round in (1, 2, 4, 8):
        acc, cost, us = _evaluate(
            lambda t: run_minions(local, remote, t.context, t.query,
                                  MinionSConfig(
                                      num_tasks_per_round=n_tasks_round)),
            tasks)
        emit(f"fig5/tasks_per_round_{n_tasks_round}", us,
             f"acc={acc:.3f};usd={cost:.4f}")
    for samples in (1, 2, 4):
        acc, cost, us = _evaluate(
            lambda t: run_minions(local, remote, t.context, t.query,
                                  MinionSConfig(num_samples=samples)),
            tasks)
        emit(f"fig5/samples_{samples}", us, f"acc={acc:.3f};usd={cost:.4f}")
    for ppc in (20, 10, 5, 2):
        acc, cost, us = _evaluate(
            lambda t: run_minions(local, remote, t.context, t.query,
                                  MinionSConfig(pages_per_chunk=ppc)),
            tasks)
        emit(f"fig5/pages_per_chunk_{ppc}", us,
             f"acc={acc:.3f};usd={cost:.4f}")


# ===========================================================================
# Figures 6-7: sequential communication
# ===========================================================================


def fig6_rounds(n_tasks: int):
    tasks = make_dataset(n_tasks, seed=31, n_pages=60)
    remote = ScriptedRemote(seed=0)
    local = SimulatedLocal("llama-3b", seed=0)
    for rounds in (1, 2, 3, 5):
        acc, cost, us = _evaluate(
            lambda t: run_minion(local, remote, t.context, t.query,
                                 MinionConfig(max_rounds=rounds)), tasks)
        emit(f"fig6/minion_rounds_{rounds}", us,
             f"acc={acc:.3f};usd={cost:.4f}")


def fig7_round_context_strategy(n_tasks: int):
    tasks = make_dataset(n_tasks, seed=41, n_pages=60)
    remote = ScriptedRemote(seed=0)
    local = SimulatedLocal("llama-3b", seed=0)
    for strategy in ("scratchpad", "retries"):
        for rounds in (1, 2, 3):
            acc, cost, us = _evaluate(
                lambda t: run_minions(
                    local, remote, t.context, t.query,
                    MinionSConfig(max_rounds=rounds,
                                  context_strategy=strategy)), tasks)
            emit(f"fig7/{strategy}_rounds_{rounds}", us,
                 f"acc={acc:.3f};usd={cost:.4f}")


# ===========================================================================
# Figure 8 / §6.5: RAG comparison
# ===========================================================================


def fig8_rag(n_tasks: int):
    tasks = make_dataset(n_tasks, seed=51, n_pages=120)
    remote = ScriptedRemote(seed=0)
    local = SimulatedLocal("llama-8b", seed=0)
    for top_k in (5, 10, 25, 50):
        acc, cost, us = _evaluate(
            lambda t: run_rag(remote, t.context, t.query, top_k=top_k),
            tasks)
        emit(f"fig8/rag_bm25_top{top_k}", us, f"acc={acc:.3f};usd={cost:.4f}")
    acc, cost, us = _evaluate(
        lambda t: run_minions(local, remote, t.context, t.query,
                              MinionSConfig()), tasks)
    emit("fig8/minions_8b", us, f"acc={acc:.3f};usd={cost:.4f}")


# ===========================================================================
# Appendix C: latency models + Prop C.1
# ===========================================================================


def appendix_c_latency(n_tasks: int):
    bound = prop_c1_bound(LLAMA_8B, LLAMA_405B, RTX_4090, H100_NODE, a=0.2)
    emit("appc/prop_c1_bound", 0.0, f"bound={bound:.2f}x;paper=4.75x")
    n = 120_000
    for c in (5, 10, 20):
        ratio = minions_latency_ratio(
            LLAMA_8B, LLAMA_405B, RTX_4090, H100_NODE, n=n, c=c, k=3, s=1,
            p_keep=0.3, n_out_local=120, n_out_remote=400)
        emit(f"appc/minions_latency_c{c}", 0.0,
             f"ratio={ratio:.2f}x;bound={bound:.2f}x")


# ===========================================================================
# Kernel microbenchmarks (interpret mode on CPU; shapes are TPU-aligned)
# ===========================================================================


def kernels(n_tasks: int):
    from repro.kernels import chunked_prefill, gqa_decode
    from repro.kernels.ref import chunked_prefill_ref, gqa_decode_ref
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    b, s, h, hd = 1, 1024, 4, 128
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, h, hd))
    v = jax.random.normal(ks[2], (b, s, h, hd))
    seg = (jnp.arange(s) // 256)[None].astype(jnp.int32)
    for name, fn in (("pallas_interp", chunked_prefill),
                     ("jnp_ref", chunked_prefill_ref)):
        fn(q, k, v, seg)  # warm/compile
        t0 = time.time()
        jax.block_until_ready(fn(q, k, v, seg))
        us = (time.time() - t0) * 1e6
        flops = 4 * b * s * 256 / 2 * h * hd  # block-diag causal
        emit(f"kernels/chunked_prefill_{name}", us,
             f"gflop={flops / 1e9:.3f}")
    lcache = 4096
    qd = jax.random.normal(ks[0], (b, h, hd))
    kc = jax.random.normal(ks[1], (b, lcache, 1, hd))
    vc = jax.random.normal(ks[2], (b, lcache, 1, hd))
    valid = jnp.array([lcache], jnp.int32)
    for name, fn in (("pallas_interp", gqa_decode), ("jnp_ref",
                                                     gqa_decode_ref)):
        fn(qd, kc, vc, valid)
        t0 = time.time()
        jax.block_until_ready(fn(qd, kc, vc, valid))
        us = (time.time() - t0) * 1e6
        emit(f"kernels/gqa_decode_{name}", us,
             f"cache_mb={lcache * hd * 2 * 4 / 2**20:.1f}")


# ===========================================================================
# Engine: fused decode throughput + prefill padding waste
# ===========================================================================


def engine_bench(n_tasks: int):
    """Decode tokens/sec through the fused while_loop, prefill padding
    waste with/without job packing, and continuous-batching vs convoy
    throughput on a ragged-budget batch; writes the BENCH_engine.json
    baseline that later PRs diff against."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as model_lib
    from repro.serving import InferenceEngine

    cfg = get_smoke_config("llama3.2-1b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    # ragged MinionS-style worker batch: many short jobs, one long outlier
    prompts = [f"worker job {i}: extract the figure from: " + "data " * (4 * (i % 5))
               for i in range(12)]
    max_new = 32

    baseline = {}
    for packed in (False, True):
        eng = InferenceEngine(cfg, params, max_seq_len=1024,
                              pack_jobs=packed)
        eng.generate_batch(prompts, max_new_tokens=max_new)  # warm/compile
        d0, t0 = eng.usage.decode_tokens, time.time()
        eng.generate_batch(prompts, max_new_tokens=max_new)
        dt = time.time() - t0
        decoded = eng.usage.decode_tokens - d0
        tok_s = decoded / max(dt, 1e-9)
        pad_frac = 1.0 - eng.usage.prefill_tokens / max(
            eng.usage.prefill_slots, 1)
        transfers = eng.usage.host_transfers / eng.usage.calls
        mode = "packed" if packed else "unpacked"
        emit(f"engine/decode_{mode}", dt * 1e6,
             f"tok_per_s={tok_s:.1f};pad_frac={pad_frac:.3f};"
             f"transfers_per_call={transfers:.1f}")
        baseline[mode] = {"decode_tok_per_s": round(tok_s, 1),
                          "prefill_pad_frac": round(pad_frac, 4),
                          "host_transfers_per_call": transfers,
                          "decode_tokens": int(decoded)}

    # --- continuous batching vs convoy on ragged per-job budgets --------
    # MinionS rounds mix quick extractions with a few long syntheses; the
    # figure of merit is USEFUL tokens/sec (sum of per-job budgets /
    # wall-clock).  The convoy baseline is the pre-PR2 EngineClient path:
    # fixed submission-order slices where every group decodes to its
    # longest member's budget.
    budgets = [8, 8, 8, 96, 8, 8, 8, 96, 8, 8, 8, 96]
    useful = sum(budgets)
    slots = 4

    def convoy(eng):
        for off in range(0, len(prompts), slots):
            grp = slice(off, off + slots)
            eng.generate_batch(prompts[grp],
                               max_new_tokens=max(budgets[grp]))

    def continuous(eng):
        eng.serve(prompts, max_new_tokens=budgets, slots=slots)

    for mode, run in (("convoy", convoy), ("continuous", continuous)):
        eng = InferenceEngine(cfg, params, max_seq_len=1024)
        run(eng)                             # warm/compile all shapes
        d0, t0 = eng.usage.decode_tokens, time.time()
        run(eng)
        dt = time.time() - t0
        decoded = eng.usage.decode_tokens - d0
        useful_tok_s = useful / max(dt, 1e-9)
        emit(f"engine/ragged_{mode}", dt * 1e6,
             f"useful_tok_per_s={useful_tok_s:.1f};"
             f"decoded={decoded};useful={useful}")
        baseline[f"ragged_{mode}"] = {
            "useful_tok_per_s": round(useful_tok_s, 1),
            "decode_tokens": int(decoded),
            "useful_tokens": useful}

    # --- prefix reuse: paged KV vs dense on shared-instruction jobs -----
    # The MinionS traffic shape: every worker job in a round repeats the
    # same task instruction and differs only in its document chunk.  With
    # dense caches each row prefills the full prompt; the paged engine
    # radix-matches the shared prefix, prefills only the novel suffix and
    # refcounts the instruction's pages across all rows AND across calls.
    # Figure of merit: prefill tokens (acceptance: paged >= 2x fewer),
    # useful tok/s and the cache HBM high-water.
    instruction = ("You are a worker model. Extract the revenue figure "
                   "for the requested fiscal year from the document chunk "
                   "below. Answer strictly as JSON with keys answer and "
                   "citation, and abstain when the figure is absent from "
                   "this chunk. " * 4)[:512]
    pjobs = [instruction + f" chunk {i}: " + f"fact-{i} row " * 8
             for i in range(12)]
    pbudget, pslots = 16, 12              # one admission wave
    prefix = {"jobs": len(pjobs), "shared_prefix_chars": len(instruction),
              "budget": pbudget}
    for mode in ("dense", "paged"):
        eng = InferenceEngine(cfg, params, max_seq_len=1024,
                              paged=(mode == "paged"), page_size=64,
                              num_pages=512)
        p0 = eng.usage.prefill_tokens
        eng.serve(pjobs, max_new_tokens=pbudget, slots=pslots)
        cold_prefill = eng.usage.prefill_tokens - p0
        # warmed repeat: compiled executables for both; the paged engine
        # additionally serves the whole prompt set from its radix
        p0, t0 = eng.usage.prefill_tokens, time.time()
        eng.serve(pjobs, max_new_tokens=pbudget, slots=pslots)
        dt = time.time() - t0
        warm_prefill = eng.usage.prefill_tokens - p0
        tok_s = len(pjobs) * pbudget / max(dt, 1e-9)
        emit(f"engine/prefix_reuse_{mode}", dt * 1e6,
             f"prefill_tokens={cold_prefill};warm_prefill={warm_prefill};"
             f"useful_tok_per_s={tok_s:.1f};"
             f"hit_tokens={eng.usage.prefix_hit_tokens};"
             f"cache_hbm_mb={eng.usage.cache_hbm_bytes / 2**20:.1f}")
        prefix[mode] = {
            "prefill_tokens": int(cold_prefill),
            "warm_prefill_tokens": int(warm_prefill),
            "useful_tok_per_s": round(tok_s, 1),
            "prefix_hit_tokens": int(eng.usage.prefix_hit_tokens),
            "prefill_tokens_saved": int(eng.usage.prefill_tokens_saved),
            "pages_allocated": int(eng.usage.pages_allocated),
            "pages_reused": int(eng.usage.pages_reused),
            "cache_hbm_bytes": int(eng.usage.cache_hbm_bytes)}
    prefix["prefill_reduction_x"] = round(
        prefix["dense"]["prefill_tokens"]
        / max(prefix["paged"]["prefill_tokens"], 1), 2)
    emit("engine/prefix_reuse", 0.0,
         f"prefill_reduction={prefix['prefill_reduction_x']}x;"
         f"warm_reduction={prefix['dense']['warm_prefill_tokens'] / max(prefix['paged']['warm_prefill_tokens'], 1):.1f}x")
    baseline["prefix_reuse"] = prefix

    # --- sharded vs single-device serve on the host mesh ----------------
    # Run under XLA_FLAGS=--xla_force_host_platform_device_count=8 to get
    # an 8-device host mesh on CPU.  Decode rows shard over the "data"
    # axis; the figure of merit is useful tok/s plus the token-identity
    # and host-transfer-parity observables (on forced host devices the
    # shards share the same cores, so wall-clock measures SPMD partition
    # overhead — the throughput win needs real parallel hardware).
    n_dev = len(jax.devices())
    if n_dev >= 2:
        from repro.launch.mesh import make_host_mesh
        sprompts = [f"shard job {i}: extract: " + "doc " * (3 * (i % 6))
                    for i in range(16)]
        sbudgets = [8, 8, 8, 48] * 4
        s_useful = sum(sbudgets)
        s_slots = 8
        outs = {}
        for mode, mesh in (("single", None), ("sharded", make_host_mesh(1))):
            eng = InferenceEngine(cfg, params, max_seq_len=1024, mesh=mesh)
            eng.serve(sprompts, max_new_tokens=sbudgets, slots=s_slots)
            d0, h0 = eng.usage.decode_tokens, eng.usage.host_transfers
            t0 = time.time()
            outs[mode] = eng.serve(sprompts, max_new_tokens=sbudgets,
                                   slots=s_slots)
            dt = time.time() - t0
            tok_s = s_useful / max(dt, 1e-9)
            transfers = eng.usage.host_transfers - h0
            emit(f"engine/serve_{mode}_{n_dev}dev", dt * 1e6,
                 f"useful_tok_per_s={tok_s:.1f};transfers={transfers}")
            baseline[f"serve_{mode}"] = {
                "useful_tok_per_s": round(tok_s, 1),
                "decode_tokens": int(eng.usage.decode_tokens - d0),
                "host_transfers": int(transfers)}
        baseline["serve_sharded"]["devices"] = n_dev
        baseline["serve_sharded"]["token_identical_to_single"] = \
            outs["sharded"] == outs["single"]

    # the device layout is part of the baseline's identity: forcing N
    # logical host devices splits the CPU N ways, so throughput numbers
    # are only comparable across runs with the same "devices" value
    with open("BENCH_engine.json", "w") as f:
        json.dump({"config": cfg.name, "devices": n_dev,
                   "n_jobs": len(prompts),
                   "max_new_tokens": max_new, "ragged_budgets": budgets,
                   "ragged_slots": slots, **baseline}, f, indent=2)
        f.write("\n")


# ===========================================================================
# Protocol tier: concurrent vs serial multi-task MinionS on one shared pool
# ===========================================================================


def _slot_occupancy(events, slots: int) -> float:
    """Estimate slot-pool occupancy from EngineUsage admit/finish events.

    A job occupies its row from admit position to finish position; pool
    capacity over an epoch is ``slots`` rows times the epoch's decode
    span.  Epochs are segmented where an admit's position drops below the
    running max (serve positions only grow within a cache epoch).
    Returns occupied row-tokens / capacity row-tokens in [0, 1]."""
    occupied = capacity = 0
    open_at, lo, hi = {}, None, None

    def flush():
        nonlocal occupied, capacity, lo, hi
        if lo is not None and hi is not None and hi > lo:
            capacity += slots * (hi - lo)
        lo = hi = None

    for kind, job, pos, _row in events:
        if kind == "admit":
            if hi is not None and pos < hi and not open_at:
                flush()
            open_at[job] = pos
            lo = pos if lo is None else min(lo, pos)
        elif kind == "finish" and job in open_at:
            occupied += pos - open_at.pop(job)
        hi = pos if hi is None else max(hi, pos)
    flush()
    return occupied / capacity if capacity else 0.0


def protocol_scenario(n_tasks: int = 6, *, n_pages: int = 2,
                      worker_max_tokens: int = 32, slots: int = 4,
                      max_seq_len: int = 4096, warm: bool = True) -> Dict:
    """Concurrent-vs-serial multi-task MinionS over ONE engine-backed pool
    (simulated remote + real engine workers).  Returns per-mode wall
    clock, drains, engine serve calls, decode tok/s and slot occupancy —
    the figure of merit is cross-task batching: same jobs, fewer drains.

    Also the fast-variant entry point for the smoke test suite."""
    from repro.configs import get_smoke_config
    from repro.core import MinionSConfig, ProtocolRunner, TaskSpec
    from repro.core.clients import EngineClient
    from repro.models import transformer as model_lib
    from repro.serving import InferenceEngine

    cfg = get_smoke_config("llama3.2-1b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    engine = InferenceEngine(cfg, params, max_seq_len=max_seq_len,
                             truncate_long=True)
    local = EngineClient(engine, "bench-engine", max_batch=slots)
    remote = ScriptedRemote(seed=0)
    pcfg = MinionSConfig(max_rounds=1, num_tasks_per_round=1,
                         pages_per_chunk=1,
                         worker_max_tokens=worker_max_tokens)
    from repro.core.tasks import make_task
    tasks = [make_task(800 + i, n_pages=n_pages, kind="extract")
             for i in range(n_tasks)]
    # explicit task_ids pin each task's PRNG identity, so serial and
    # concurrent execution sample the same worker tokens
    specs = [TaskSpec("minions", t.context, t.query, pcfg, task_id=i)
             for i, t in enumerate(tasks)]
    runner = ProtocolRunner(local, remote)

    def serial():
        return [runner.run([s])[0] for s in specs]

    def concurrent():
        return runner.run(specs)

    if warm:   # compile every shape both modes will hit
        serial()
        concurrent()

    out: Dict[str, Dict] = {"n_tasks": n_tasks, "slots": slots}
    answers = {}
    for mode, run in (("serial", serial), ("concurrent", concurrent)):
        d0 = engine.usage.decode_tokens
        c0 = engine.usage.calls
        # the event log trims its FRONT at MAX_EVENTS, so a saved length
        # offset goes stale — clear it and read the whole log per run
        # (still truncated if ONE run exceeds MAX_EVENTS admit/finishes)
        engine.usage.events.clear()
        dr0 = runner.scheduler.drains
        t0 = time.time()
        results = run()
        dt = time.time() - t0
        answers[mode] = [r.answer for r in results]
        decoded = engine.usage.decode_tokens - d0
        out[mode] = {
            "wall_s": round(dt, 3),
            "drains": runner.scheduler.drains - dr0,
            "engine_serve_calls": engine.usage.calls - c0,
            "decode_tok_per_s": round(decoded / max(dt, 1e-9), 1),
            "slot_occupancy": round(_slot_occupancy(
                engine.usage.events, slots), 4),
        }
    out["answers_identical"] = answers["serial"] == answers["concurrent"]
    return out


def fault_scenario(n_tasks: int = 8,
                   rates=(0.0, 0.15, 0.3, 0.5), seed: int = 0) -> Dict:
    """Goodput vs injected remote fault rate: N concurrent MinionS tasks
    per rate over one shared pool, the remote wrapped in a seeded
    FaultyClient (errors + stalls) behind a ResilientClient (timeout,
    retries, circuit breaker).  Goodput = fraction of tasks that still
    produce an answer (ok or degraded); the statuses/attempt counters
    show WHERE the supervision layer absorbed the faults."""
    from repro.core import (MinionSConfig, ProtocolRunner, ResilientClient,
                            TaskSpec)
    from repro.core.faults import FaultyClient
    from repro.core.tasks import make_dataset as _mk

    tasks = _mk(n_tasks, seed=13, n_pages=8)
    local = SimulatedLocal("llama-8b", seed=0)
    cfg = MinionSConfig(max_rounds=2)
    out: Dict = {"n_tasks": n_tasks, "seed": seed, "rates": []}
    for rate in rates:
        faulty = FaultyClient(ScriptedRemote(seed=0), seed=seed,
                              error_rate=rate * 0.6,
                              timeout_rate=rate * 0.4)
        # deadline above the latency model's clean envelope (a 1024-token
        # decompose draws ~2.1-2.5s) but far below a 60s stall, so only
        # injected faults trip it
        remote = ResilientClient(faulty, timeout_s=4.0, max_retries=2,
                                 seed=seed, breaker_threshold=6,
                                 breaker_cooldown=8)
        runner = ProtocolRunner(local, remote)
        t0 = time.time()
        results = runner.run(
            [TaskSpec("minions", t.context, t.query, cfg, task_id=i)
             for i, t in enumerate(tasks)])
        dt = time.time() - t0
        statuses: Dict[str, int] = {}
        for r in results:
            statuses[r.status] = statuses.get(r.status, 0) + 1
        answered = sum(r.answer is not None for r in results)
        correct = sum(score_answer(r.answer, t.answer)
                      for r, t in zip(results, tasks))
        row = {
            "fault_rate": rate,
            "wall_s": round(dt, 3),
            "goodput": round(answered / n_tasks, 3),
            "accuracy": round(correct / n_tasks, 3),
            "statuses": statuses,
            "remote_attempts": remote.stats.attempts,
            "retries": remote.stats.retries,
            "timeouts": remote.stats.timeouts,
            "breaker_opens": remote.stats.breaker_opens,
            "fast_failures": remote.stats.fast_failures,
            "degradations": runner.degradations,
            "simulated_remote_s": round(faulty.simulated_s, 2),
            # every attempt (failed retries included) stays on the bill
            "attempt_prefill_tokens": remote.meter.usage.prefill_tokens,
        }
        out["rates"].append(row)
        emit(f"protocol/faults_rate_{rate}", dt / n_tasks * 1e6,
             f"goodput={row['goodput']};acc={row['accuracy']};"
             f"statuses={'/'.join(f'{k}:{v}' for k, v in statuses.items())};"
             f"retries={row['retries']};"
             f"breaker_opens={row['breaker_opens']};"
             f"degradations={row['degradations']}")
    return out


def protocol_bench(n_tasks: int):
    """Emit the concurrent-vs-serial protocol scenario plus the
    goodput-under-fault-rate sweep and merge both into the
    BENCH_engine.json baseline (key "protocol")."""
    res = protocol_scenario(min(n_tasks, 8))
    for mode in ("serial", "concurrent"):
        m = res[mode]
        emit(f"protocol/minions_{mode}", m["wall_s"] * 1e6,
             f"drains={m['drains']};serve_calls={m['engine_serve_calls']};"
             f"tok_per_s={m['decode_tok_per_s']};"
             f"occupancy={m['slot_occupancy']}")
    emit("protocol/cross_task_batching", 0.0,
         f"drain_reduction={res['serial']['drains']}->"
         f"{res['concurrent']['drains']};"
         f"answers_identical={res['answers_identical']}")
    res["goodput_vs_fault_rate"] = fault_scenario(min(n_tasks, 8))
    path = "BENCH_engine.json"
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["protocol"] = res
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")


# ===========================================================================
# Fleet tier: 2-replica heterogeneous EnginePool vs a single replica
# ===========================================================================


def fleet_scenario(n_tasks: int = 8, *, worker_max_tokens: int = 32,
                   slots: int = 4, cost_weight: float = 0.001) -> Dict:
    """The same concurrent MinionS workload through ONE ProtocolRunner
    over (a) a single-replica pool and (b) a 2-replica heterogeneous
    fleet — a cheap dense tier (cost 1.0) plus a costly paged tier
    (cost 3.0) behind cost-aware routing.  Records wall clock, goodput,
    decode tok/s, the routing split, cache counters and requeues; the
    determinism check is that BOTH pools produce identical answers
    (placement-independent PRNG lanes — routing moves jobs, not
    tokens)."""
    from repro.core import MinionSConfig, ProtocolRunner, TaskSpec
    from repro.core.tasks import make_task
    from repro.launch.serve import build_engine
    from repro.serving import EnginePool, Replica

    def make_pool(two: bool) -> EnginePool:
        replicas = [Replica(
            build_engine("llama3.2-1b", truncate_long=True),
            name="cheap", cost_per_token=1.0, max_batch=slots)]
        if two:
            replicas.append(Replica(
                build_engine("llama3.2-1b", truncate_long=True,
                             paged=True, page_size=32),
                name="costly", cost_per_token=3.0, max_batch=slots))
        return EnginePool(replicas, route_by_cost=True,
                          cost_weight=cost_weight)

    pcfg = MinionSConfig(max_rounds=1, num_tasks_per_round=1,
                         pages_per_chunk=1,
                         worker_max_tokens=worker_max_tokens)
    tasks = [make_task(900 + i, n_pages=2, kind="extract")
             for i in range(n_tasks)]
    specs = [TaskSpec("minions", t.context, t.query, pcfg, task_id=i)
             for i, t in enumerate(tasks)]

    out: Dict[str, Dict] = {"n_tasks": n_tasks, "slots": slots,
                            "cost_weight": cost_weight,
                            "note": "per-replica drains are sequential "
                                    "within a gateway drain and the paged "
                                    "tier pays interpret-mode overhead on "
                                    "CPU, so two-replica wall clock here "
                                    "measures routing/goodput/identity, "
                                    "not fleet speedup (see ROADMAP fleet "
                                    "follow-ons)"}
    answers = {}
    for mode, two in (("one_replica", False), ("two_replica", True)):
        pool = make_pool(two)
        runner = ProtocolRunner(pool, ScriptedRemote(seed=0))
        runner.run(specs)          # warm: compile every shape
        for rep in pool.replicas:
            rep.served_jobs = rep.decode_tokens = 0
        pool.usage.reset()
        t0 = time.time()
        results = runner.run(specs)
        dt = time.time() - t0
        answers[mode] = [r.answer for r in results]
        decoded = sum(rep.decode_tokens for rep in pool.replicas)
        out[mode] = {
            "wall_s": round(dt, 3),
            "goodput": round(sum(r.status == "ok" for r in results)
                             / n_tasks, 3),
            "gateway_drains": pool.usage.drains,
            "jobs_drained": pool.usage.jobs_drained,
            "decode_tok_per_s": round(decoded / max(dt, 1e-9), 1),
            "routing": {rep.name: rep.served_jobs
                        for rep in pool.replicas},
            "cache": {"hits": pool.usage.cache_hits,
                      "misses": pool.usage.cache_misses,
                      "bypass": pool.usage.cache_bypass},
            "requeues": pool.usage.requeues,
        }
    out["answers_identical"] = \
        answers["one_replica"] == answers["two_replica"]
    return out


def fleet_bench(n_tasks: int):
    """Emit the 2-replica-vs-1-replica fleet scenario and merge it into
    the BENCH_engine.json baseline (key "fleet")."""
    res = fleet_scenario(min(n_tasks, 8))
    for mode in ("one_replica", "two_replica"):
        m = res[mode]
        routing = "/".join(f"{k}:{v}" for k, v in m["routing"].items())
        emit(f"fleet/minions_{mode}", m["wall_s"] * 1e6,
             f"goodput={m['goodput']};tok_per_s={m['decode_tok_per_s']};"
             f"drains={m['gateway_drains']};routing={routing};"
             f"requeues={m['requeues']}")
    emit("fleet/placement_identity", 0.0,
         f"answers_identical={res['answers_identical']}")
    path = "BENCH_engine.json"
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["fleet"] = res
    with open(path, "w") as f:
        json.dump(data, f, indent=2)
        f.write("\n")


# ===========================================================================
# Roofline summary (reads the dry-run artifacts)
# ===========================================================================


def roofline_summary(n_tasks: int):
    paths = sorted(glob.glob("experiments/dryrun/*.json"))
    if not paths:
        emit("roofline/none", 0.0, "run repro.launch.dryrun first")
        return
    for p in paths:
        with open(p) as f:
            d = json.load(f)
        rl = d["roofline"]
        emit(f"roofline/{d['arch']}_{d['shape']}_{d['mesh']}", 0.0,
             f"compute_ms={rl['compute_s'] * 1e3:.2f};"
             f"memory_ms={rl['memory_s'] * 1e3:.2f};"
             f"collective_ms={rl['collective_s'] * 1e3:.2f};"
             f"bound={rl['bottleneck']}")


BENCHMARKS: Dict[str, Callable] = {
    "table1": table1_cost_accuracy,
    "fig3_context": fig3_context_length,
    "fig3_multistep": fig3_multistep,
    "fig5": fig5_parallel_scaling,
    "fig6": fig6_rounds,
    "fig7": fig7_round_context_strategy,
    "fig8_rag": fig8_rag,
    "appendix_c": appendix_c_latency,
    "kernels": kernels,
    "engine": engine_bench,
    "protocol": protocol_bench,
    "fleet": fleet_bench,
    "roofline": roofline_summary,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=sorted(BENCHMARKS))
    ap.add_argument("--tasks", type=int, default=12)
    args = ap.parse_args()
    compile_cache.configure()
    print("name,us_per_call,derived")
    for name, fn in BENCHMARKS.items():
        if args.only and name != args.only:
            continue
        fn(args.tasks)
    os.makedirs("experiments", exist_ok=True)
    # merge with the existing CSV so a partial (--only) run refreshes its
    # own rows without dropping the other benchmarks' recorded baselines
    path = "experiments/bench_results.csv"
    fresh = {r.split(",", 1)[0]: r for r in ROWS}
    merged: List[str] = []
    if os.path.exists(path):
        with open(path) as f:
            for line in f.read().splitlines()[1:]:
                name = line.split(",", 1)[0]
                merged.append(fresh.pop(name, line))
    merged += [fresh[n] for n in (r.split(",", 1)[0] for r in ROWS)
               if n in fresh]
    with open(path, "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(merged) + "\n")


if __name__ == "__main__":
    main()
