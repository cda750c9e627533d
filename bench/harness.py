"""One run of one cell: set up, warm up, measure, check, report.

The window drives ``ProtocolRunner.run`` on MinionS tasks, with an
``EngineClient`` over one paged ``InferenceEngine`` on the Pallas kernels
and the repo's ``ScriptedRemote`` on the host.  The benchmark observes the
engine from outside: a recording tokenizer (a constructor argument) keeps
each served token row, a wrapper around ``engine.serve`` opens the
"local batch" span and pairs those rows with the jobs through the engine's
own "finish" events, and ``EngineUsage`` counters are read as deltas.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

from . import model
from . import reference, trace as trace_lib, traffic as traffic_lib, work
from .spec import Spec

KEPT_OPS = 4000
COUNTERS = ("prefix_hit_tokens", "host_transfers")
# the control of each served precision: the nearest precision below it
CONTROL_QUANT = {"bfloat16": "fp8", "float16": "fp8", "float32": "bf16"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class ProgramEvents:
    """Programs the process had to produce, from ``jax.monitoring``: each
    backend compile, and whether it was a load from the persistent cache
    (the cache-hit event fires inside the compile it shortens) or a real
    compile, by the jitted function's name."""

    def __init__(self, jax):
        self.produced = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.compiled: List[str] = []     # names of programs that missed
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.produced += 1
            self.compile_s += duration
            if not self._hit:
                self.compiled.append(f"{fun_name} ({duration:.2f} s)")
            self._hit = False

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
            self._hit = True


@dataclasses.dataclass
class ServedJob:
    prompt: tuple
    served: Optional[tuple]       # None: the engine never finished the job
    temperature: float
    budget: int
    call: int


def attach_recorder(engine, annotate: Callable):
    """Record every job ``engine.serve`` answers (prompt ids, served ids,
    temperature, budget) into the returned list, and wrap each serve call
    in the "local batch" span."""
    from repro.serving.tokenizer import ByteTokenizer

    tok = engine.tokenizer
    if type(tok).decode is ByteTokenizer.decode:
        raise TypeError("engine needs the RecordingTokenizer")
    jobs: List[ServedJob] = []
    state = {"call": 0, "finished": {}}
    record, serve = engine.usage.record, engine.serve

    def on_record(kind, job, pos, row):
        if kind == "finish":     # comes right after that row's decode
            state["finished"][job] = tuple(tok.last)
        record(kind, job, pos, row)

    def on_serve(prompts, **kw):
        state["finished"] = {}
        with annotate("local batch"):
            texts = serve(prompts, **kw)
        n = len(prompts)
        temps, budgets = (v if isinstance(v, (list, tuple)) else [v] * n
                          for v in (kw.get("temperature", 0.0),
                                    kw.get("max_new_tokens", 128)))
        for j, p in enumerate(prompts):
            jobs.append(ServedJob(tuple(tok.encode(p)),
                                  state["finished"].get(j), float(temps[j]),
                                  int(budgets[j]), state["call"]))
        return texts

    engine.usage.record = on_record
    engine.serve = on_serve
    return jobs, state


def recording_tokenizer():
    from repro.serving.tokenizer import ByteTokenizer

    class RecordingTokenizer(ByteTokenizer):
        """The byte tokenizer, keeping the ids of its last ``decode``."""
        last: tuple = ()

        def decode(self, ids):
            self.last = tuple(int(i) for i in ids)
            return super().decode(ids)

    return RecordingTokenizer()


@dataclasses.dataclass
class Context:
    """What a metric reader sees: ``arch`` is the configuration's
    architecture module, ``dims`` its sizes."""
    arch: ModuleType
    dims: Any
    peak: Optional[dict]
    jobs: List[work.Job]
    usage: Dict[str, int]
    window_programs: int
    tasks: int
    window_s: float
    setup_s: float
    trace: Optional[dict] = None
    trace_summary: Optional[dict] = None
    _counts: Optional[work.Counts] = None

    @property
    def counts(self) -> work.Counts:
        if self._counts is None:
            self._counts = work.counts(self.jobs)
        return self._counts

    def kernel_seconds(self, pattern: str) -> float:
        s = self.trace_summary
        plane = s["planes"][0]
        return trace_lib.kernel_seconds(self.trace["device"][plane], pattern,
                                        s["lo"], s["hi"])

    def log(self, msg: str) -> None:
        log(msg)


def trace_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def peak_for(kind: str, spec: Spec) -> dict:
    peaks = json.loads((spec.bench / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, spec: Optional[Spec] = None,
        rehearse: Optional[dict] = None, keep_trace: Optional[str] = None,
        control: bool = False) -> dict:
    """Run one cell once and return its result line (a dict).

    ``rehearse`` (CPU only: {"dims": {...}, "mix": {...}, "backend": ...,
    "limits": {...}}) shrinks the configuration and the mix and skips the
    look for a chip; ``keep_trace`` writes the first ``KEPT_OPS`` device ops
    of the traced window, the spans around them and the window's op totals
    (a fixture); ``control`` also judges the control, the reference at the
    next precision down put in the program's place, on the same sample, and
    reports its own verdict under "control" (``bench/control.py``; the
    benchmark's own runs never do)."""
    import jax

    spec = spec or Spec()
    cell = spec.workload(workload)
    devices = jax.devices()
    dev = devices[0]
    if rehearse is None:
        if dev.platform != "tpu":
            raise SystemExit(f"bench: no TPU (JAX backend {dev.platform})")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"bench: {workload} needs {cell['chips']} "
                             f"chips, JAX sees {len(devices)}")
    peak = peak_for(dev.device_kind, spec) if dev.platform == "tpu" else None

    cache_dir = "off (rehearsal)"
    if rehearse is None:
        from repro.launch import compile_cache
        cache_dir = compile_cache.configure()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    events = ProgramEvents(jax)

    mix = dict(spec.traffic(cell["traffic"]))
    config = spec.config(cell["config"])
    arch = spec.arch_module(config["model_type"])
    dims = arch.load_dims(config)
    backend = "pallas" if rehearse is None else rehearse.get("backend",
                                                              "reference")
    limits = spec.limits(workload)
    if rehearse is not None:
        mix.update(rehearse.get("mix", {}))
        dims = dataclasses.replace(dims, **rehearse.get("dims", {}))
        limits.update(rehearse.get("limits", {}))
    log(f"bench: {workload} seed {seed} on {dev.platform} {dev.device_kind} "
        f"x{len(devices)}; compile cache {cache_dir}; bytes_limit "
        f"{(dev.memory_stats() or {}).get('bytes_limit')}; {dims}")

    from repro.core import ProtocolRunner
    from repro.core.clients import EngineClient
    from repro.core.simulated import ScriptedRemote
    from repro.serving import InferenceEngine

    params = model.make_weights(arch, dims,
                                traffic_lib.derive(seed, "weights"))
    engine = InferenceEngine(
        arch.program_config(dims, backend), params,
        tokenizer=recording_tokenizer(), max_seq_len=dims.max_seq_len,
        paged=True, page_size=dims.page_size, num_pages=dims.num_pages)
    jobs, state = attach_recorder(engine, jax.profiler.TraceAnnotation)
    client = EngineClient(engine, max_batch=dims.slots,
                          seed=traffic_lib.derive(seed, "lanes") & 0x7FFFFFFF)
    runner = ProtocolRunner(client, ScriptedRemote(
        seed=traffic_lib.derive(seed, "remote") & 0x7FFFFFFF))

    def one_call(stream: str, call: int):
        specs = traffic_lib.call_specs(mix, seed, stream, call)
        state["call"] = call
        with jax.profiler.TraceAnnotation("protocol round"):
            return runner.run(specs)

    # warm-up: the mix's own number of calls, on documents of a stream of
    # their own (the window's are never served before the window), so the
    # window meets programs already made and a page pool in its steady state
    for w in range(mix["warmup_calls"]):
        before = events.produced
        one_call("warmup", w)
        log(f"bench: warm-up call {w}: {events.produced - before} programs "
            f"({events.produced} so far, {events.cache_hits} of them from "
            "the persistent cache)")
    log(f"bench: compiled in set-up, not found in the persistent cache: "
        f"{events.compiled}")
    missed = len(events.compiled)
    del jobs[:]

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    u0 = {k: getattr(engine.usage, k) for k in COUNTERS}
    p0 = events.produced
    results = []
    if traced:
        # host spans from TraceMe annotations, not every Python call
        jax.profiler.start_trace(trace_dir, profiler_options=trace_options(jax))
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    call = 0
    while True:
        results += one_call("window", call)
        call += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if traced:
        jax.profiler.stop_trace()
    usage = {k: getattr(engine.usage, k) - u0[k] for k in COUNTERS}
    window_programs = events.produced - p0
    stats = [d.memory_stats() or {} for d in devices[:cell["chips"]]]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"bench: peak_bytes_in_use {peak_bytes}, bytes_limit "
        f"{max(s.get('bytes_limit', 0) for s in stats)}")

    failed = sum(r.status != "ok" or r.error is not None for r in results)
    for r in results:
        if r.error is not None:
            log(f"bench: first failed task: {r.status} {r.error}")
            break
    unanswered = sum(j.served is None for j in jobs)
    served = [j for j in jobs if j.served is not None]
    early = sum(len(j.served) < j.budget for j in served)
    log(f"bench: window {window_s:.3f} s, {call} calls, {len(results)} tasks "
        f"({failed} not ok), {len(served)} jobs, {early} stopped before "
        f"their budget, {window_programs} programs produced in the window, "
        f"compiled there: {events.compiled[missed:]}")

    # free the program's state before the reference runs on the chip
    del runner, client, engine
    gc.collect()

    ctx = Context(arch=arch, dims=dims, peak=peak,
                  jobs=[work.Job(j.prompt, len(j.served), j.call)
                        for j in served],
                  usage=usage, window_programs=window_programs,
                  tasks=len(results) - failed, window_s=window_s,
                  setup_s=setup_s)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak_bytes}
    out = {}
    if traced:
        ctx.trace = trace_lib.read_xplane(trace_dir)
        log(f"bench: trace lines {ctx.trace['lines']}, "
            f"{len(ctx.trace['host'])} host spans")
        if keep_trace:
            Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            summ = trace_lib.summary(ctx.trace)
            families = summ and trace_lib.top_ops(
                ctx.trace["device"][summ["planes"][0]], summ["lo"],
                summ["hi"], top=200)
            Path(keep_trace).write_text(json.dumps(dict(
                trace_lib.head(ctx.trace, KEPT_OPS), families=families,
                counts=dataclasses.asdict(ctx.counts))))
        ctx.trace_summary = trace_lib.summary(ctx.trace)
        if ctx.trace_summary is not None:
            device["busy_s"] = ctx.trace_summary["busy_s"]
            device["window_s"] = ctx.trace_summary["window_s"]
            out["breakdown"] = ctx.trace_summary["breakdown"]
    metrics = {}
    for m in spec.metrics_for(workload, traced):
        value = spec.metric_module(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    check_args = (arch, params, dims, served, mix, limits, seed)
    counts = dict(unanswered=unanswered, failed_tasks=failed)
    check = output_check(*check_args, **counts)
    if control:
        ctl = output_check(*check_args, **counts,
                           quant=CONTROL_QUANT[dims.dtype])
        out["control"] = {"correct": verdict(ctl), "check": ctl}
        for name, c in ctl.items():
            log(f"control: {name} {c['value']!r} limit {c['limit']!r}")
    for name, c in check.items():
        log(f"check: {name} {c['value']!r} limit {c['limit']!r}")
    if traced:
        shutil.rmtree(trace_dir)
    return {"correct": verdict(check), "attempted": len(results),
            "failed": failed, "metrics": metrics, "device": device, **out,
            "check": check}


def verdict(check: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in check.values())


def check_sample(served: List[ServedJob], mix: dict, seed: int
                 ) -> List[ServedJob]:
    """Greedy window jobs drawn from the seed, the longest prompt first,
    until they hold ``check_served_tokens`` served tokens."""
    greedy = [j for j in served if j.temperature <= 0.0 and j.served]
    if not greedy:
        return []
    longest = max(greedy, key=lambda j: (len(j.prompt), j.call))
    rest = [j for j in greedy if j is not longest]
    random.Random(traffic_lib.derive(seed, "check")).shuffle(rest)
    sample, tokens = [longest], len(longest.served)
    for j in rest:
        if tokens >= mix["check_served_tokens"]:
            break
        sample.append(j)
        tokens += len(j.served)
    return sample


def output_check(arch, params, dims, served, mix, limits, seed, *,
                 unanswered: int, failed_tasks: int,
                 quant: Optional[str] = None) -> dict:
    """The numbers ``correct`` compares, each with its limit:

    - ``tasks_failed``: window tasks that did not end ``ok`` (exact, 0);
    - ``jobs_unanswered``: worker jobs handed to ``engine.serve`` in the
      window that it never finished (exact, 0);
    - ``max_logit_gap``: over a sample of greedy jobs, the widest gap by
      which a served token's reference logit lies below the reference's
      best, the reference (``arch.logits``) run over the prompt with the
      served tokens.

    With ``quant`` the control stands in the program's place: at each
    position of the same prompts and served tokens, the token that the
    reference at that lower precision puts first is judged instead."""
    sample = check_sample(served, mix, seed)
    gap = float("inf")
    if sample:
        t0 = time.perf_counter()
        gaps = []
        for j in sample:
            rows = reference.served_rows(len(j.prompt), len(j.served))
            ref = arch.logits(params, dims, j.prompt + j.served, rows)
            picked = j.served if quant is None else arch.logits(
                params, dims, j.prompt + j.served, rows,
                quant=quant).argmax(-1)
            gaps.append(reference.served_gaps(ref, picked).max())
        gap = float(max(gaps))
        log(f"check: reference{'' if quant is None else ' and ' + quant} "
            f"over {len(sample)} jobs, "
            f"{sum(len(j.served) for j in sample)} served tokens, "
            f"{time.perf_counter() - t0:.3f} s")
    return {
        "tasks_failed": {"value": failed_tasks, "limit": 0},
        "jobs_unanswered": {"value": unanswered, "limit": 0},
        "max_logit_gap": {"value": gap,
                          "limit": limits["max_logit_gap"]["limit"]},
    }
