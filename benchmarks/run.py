"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the
wall-clock microseconds per protocol query (or per kernel call);
``derived`` carries the figure-of-merit the paper's table reports
(accuracy, $/query, reduction factor, ...).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--tasks N]
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Callable, Dict, List

import jax

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
# Protocol tier: concurrent vs serial multi-task MinionS on one shared pool
# ===========================================================================


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
        s0 = engine.usage.decode_steps
        c0 = engine.usage.calls
        dr0 = runner.scheduler.drains
        t0 = time.time()
        results = run()
        dt = time.time() - t0
        answers[mode] = [r.answer for r in results]
        decoded = engine.usage.decode_tokens - d0
        steps = engine.usage.decode_steps - s0
        out[mode] = {
            "wall_s": round(dt, 3),
            "drains": runner.scheduler.drains - dr0,
            "engine_serve_calls": engine.usage.calls - c0,
            "decode_tok_per_s": round(decoded / max(dt, 1e-9), 1),
            # tokens emitted over the rows the decode loop stepped
            "slot_occupancy": round(decoded / max(slots * steps, 1), 4),
        }
    out["answers_identical"] = answers["serial"] == answers["concurrent"]
    return out


BENCHMARKS: Dict[str, Callable] = {
    "table1": table1_cost_accuracy,
    "fig3_context": fig3_context_length,
    "fig3_multistep": fig3_multistep,
    "fig5": fig5_parallel_scaling,
    "fig6": fig6_rounds,
    "fig7": fig7_round_context_strategy,
    "fig8_rag": fig8_rag,
    "appendix_c": appendix_c_latency,
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
