#!/usr/bin/env python3
"""Chip smoke test: the served MinionS path on a TPU at llama3.2-1b width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # mesh-sharded serve vs one device
    python chip_smoke.py --rehearse   # smoke config on the CPU, no chip

Everything runs in this one process, through the entry points a user
calls: ``repro.launch.serve.build_engine`` -> ``InferenceEngine.serve``
-> ``JobScheduler`` -> ``ProtocolRunner``, at the published widths of
``llama3.2-1b`` in its configured float32, with random weights from
``--seed``.

One chip:
  1. MinionS: ``N_TASKS`` concurrent tasks (as ``repro.launch.serve
     --minions`` builds them) on the dense engine, reference attention.
  2. Pallas: the same tasks on the same weights with
     ``attention_backend="pallas"``, dense and then paged.  The model's
     prefill and decode programs at that config must hold
     ``tpu_custom_call``; their prefill logits and first-decode logits
     must agree with the reference within ``LOGIT_TOL`` (relative L2).
     Greedy tokens are printed with the share that agree: the reference
     also runs at the TPU's default matmul precision, so equal tokens
     are not required.
``--chips 4``: only the mesh-sharded serve (``model`` axis 1 and 2) and
the single-device engine it is compared with, at the same tolerance, on
the reference attention (a Mosaic kernel is not partitioned by GSPMD)
and with depth cut to ``FOUR_CHIP_LAYERS`` (widths unchanged).

Any task status other than ``ok``, any row error or any failed check
exits non-zero.  Without a TPU the script exits non-zero before running
anything (``--rehearse`` runs the smoke config on the CPU instead and
reports the CPU).  Times and memory are printed as set-up information,
not metrics.  The last line of stdout is one JSON object.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llama3.2-1b"
SEED = 0
N_TASKS = 3           # concurrent MinionS tasks (two worker jobs each)
SLOTS = 4             # decode rows of the serve pool
LOGIT_TOL = 2e-2      # max relative L2 error of logits vs the reference
CHECK_ROWS, CHECK_LEN, CHECK_STEPS = 2, 512, 4   # direct logit check
# 256 pages (1 GiB of f32 KV): at 512 the paged prefill's layout copies of
# the pool (~0.5 GiB per layer) no longer fit beside the 4.6 GiB weights
PAGE_SIZE, NUM_PAGES = 64, 256
FOUR_CHIP_LAYERS = 4


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--rehearse", action="store_true",
                    help="smoke config on the CPU (never reports a TPU)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={args.chips}")

    import jax

    from repro.launch import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX backend {dev.platform}); run with "
              "--rehearse for the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()
    timer = _CompileTimer(jax)
    print(f"setup: device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            _four_chips(args, timer)
        else:
            _one_chip(args, timer)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    total = time.perf_counter() - t0
    print(f"setup: total {total:.1f}s, of which XLA compile {timer.compile_s:.1f}s"
          f" ({timer.cache_hits} persistent-cache hits); "
          f"{_peak_bytes(devices)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


class _CompileTimer:
    """Seconds of XLA backend compilation (JAX's own monitoring event; a
    persistent-cache hit skips it) and the number of cache hits.  Tracing
    and lowering are not counted: their events nest."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _phase(name, timer, fn, *a, **kw):
    t0, c0 = time.perf_counter(), timer.compile_s
    out = fn(*a, **kw)
    wall = time.perf_counter() - t0
    print(f"setup: {name}: {wall:.1f}s wall, of which XLA compile "
          f"{timer.compile_s - c0:.1f}s")
    return out


def _peak_bytes(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    if any(p is None for p in peaks):
        return "peak_bytes_in_use not reported by this backend"
    return "peak_bytes_in_use " + ", ".join(
        f"{p} ({p / 2**30:.2f} GiB)" for p in peaks)


# ---------------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------------


def _serve_minions(engine, label: str):
    """N_TASKS MinionS tasks through one ProtocolRunner over ``engine``;
    every task must end ``ok``."""
    from repro.core import ProtocolRunner
    from repro.core.clients import EngineClient
    from repro.core.simulated import ScriptedRemote
    from repro.launch.serve import minions_tasks

    runner = ProtocolRunner(EngineClient(engine, max_batch=SLOTS),
                            ScriptedRemote(seed=0))
    fb0 = engine.usage.decode_fallback_steps
    results = runner.run(minions_tasks(N_TASKS))
    for i, r in enumerate(results):
        err = f" error={r.error!r}" if r.error else ""
        print(f"{label}: task {i} status={r.status} answer={r.answer!r}{err}")
    print(f"{label}: {runner.scheduler.drains} drains / "
          f"{runner.scheduler.jobs_drained} worker jobs")
    if engine.cfg.attention_backend == "pallas" and not engine.paged:
        fb = engine.usage.decode_fallback_steps - fb0
        print(f"{label}: served decode steps on the reference branch of the "
              f"decode lax.cond: {fb} (every other step ran the kernel)")
    bad = [i for i, r in enumerate(results)
           if r.status != "ok" or r.error is not None]
    check(not bad, f"{label}: tasks {bad} did not end ok")
    return results


def _build(args):
    """The launcher's engine at published width (smoke width when
    rehearsing), weights resident on the device."""
    import jax

    from repro.launch.serve import build_engine
    engine = build_engine(ARCH, smoke=args.rehearse, seed=args.seed,
                          truncate_long=True)
    jax.block_until_ready(engine.params)
    return engine


def _one_chip(args, timer):
    from repro.serving import InferenceEngine

    engine = _phase("build engine (random weights)", timer, _build, args)
    cfg = engine.cfg
    print(f"config: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model}"
          f" heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")

    _phase("phase 1 MinionS, dense, reference attention", timer,
           _serve_minions, engine, "phase1 reference dense")

    pallas = cfg.replace(attention_backend="pallas")
    dense = InferenceEngine(pallas, engine.params,
                            max_seq_len=engine.max_seq_len,
                            truncate_long=True)
    _phase("phase 2 MinionS, dense, pallas attention", timer,
           _serve_minions, dense, "phase2 pallas dense")
    check(dense.usage.decode_fallback_steps == 0,
          "served Pallas decode steps fell back to reference attention")
    paged = InferenceEngine(pallas, engine.params,
                            max_seq_len=engine.max_seq_len,
                            truncate_long=True, paged=True,
                            page_size=PAGE_SIZE, num_pages=NUM_PAGES)
    _phase("phase 2 MinionS, paged, pallas attention", timer,
           _serve_minions, paged, "phase2 pallas paged")
    del dense, paged

    _phase("phase 2 logit check", timer, _logit_check, engine.params, cfg,
           pallas, interpret=args.rehearse)


# ---------------------------------------------------------------------------
# direct logit check: prefill + decode programs at each config
# ---------------------------------------------------------------------------


def _check_tokens(rows: int = CHECK_ROWS):
    """(rows, CHECK_LEN) byte-token prompts from the MinionS tasks."""
    import numpy as np

    from repro.launch.serve import minions_tasks
    from repro.serving.tokenizer import ByteTokenizer
    tok = ByteTokenizer()
    ids = [tok.encode(t.context + "\n" + t.query)[-CHECK_LEN:]
           for t in minions_tasks(rows)]
    return np.asarray(ids, np.int32)


def _compiled(fn, *args):
    """(compiled program, whether it holds a Pallas TPU kernel)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, "tpu_custom_call" in compiled.as_text()


def _greedy_steps(params, cfg, logits, cache, forced, kernels):
    """CHECK_STEPS decode steps after a prefill that gave ``logits``
    (B, V), fed ``forced`` tokens (or the run's own argmax when None).
    Returns (logits per step, (prefill, decode) kernel flags)."""
    import jax.numpy as jnp

    from repro.models import transformer as T
    steps = [logits]
    decode = None
    for i in range(CHECK_STEPS):
        tok = (forced[i] if forced is not None
               else jnp.argmax(steps[-1], -1).astype(jnp.int32))[:, None]
        if decode is None:
            decode, k_dec = _compiled(
                lambda p, t, c: T.decode_step(p, cfg, t, c),
                params, tok, cache)
        logits, cache = decode(params, tok, cache)
        steps.append(logits[:, -1])
    return steps, (kernels, k_dec)


def _dense_run(params, cfg, ids, forced):
    """Greedy-forced run on the dense cache."""
    from repro.models import transformer as T
    cap = 2 * CHECK_LEN
    prefill, k_pre = _compiled(
        lambda p, t: T.prefill(p, cfg, {"tokens": t}, cap), params, ids)
    logits, cache = prefill(params, ids)
    return _greedy_steps(params, cfg, logits[:, -1], cache, forced, k_pre)


def _paged_run(params, cfg, ids, forced):
    """The same run against a paged cache (page_size 64): row b owns
    consecutive pages, token i sits in page i // 64."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    b, s = ids.shape
    p_row = s // PAGE_SIZE + 1
    pages = 1 + np.arange(b * p_row, dtype=np.int32).reshape(b, p_row)
    pos = np.broadcast_to(np.arange(s, dtype=np.int32), (b, s))
    dst_page = np.take_along_axis(pages, pos // PAGE_SIZE, axis=1)
    layers = T.init_paged_cache(cfg, 1 + b * p_row, PAGE_SIZE)
    args = (params, ids, pos, pages, dst_page, pos % PAGE_SIZE, layers)
    prefill, k_pre = _compiled(
        lambda p, t, ps, pt, dp, ds, ly: T.paged_prefill(
            p, cfg, t, ps, pt, dp, ds, ly), *args)
    logits, layers = prefill(*args)
    cache = {"layers": layers, "page_table": jnp.asarray(pages),
             "row_len": jnp.full((b,), s, jnp.int32)}
    return _greedy_steps(params, cfg, logits, cache, forced, k_pre)


def _rel_err(a, ref) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm(a - ref) / jnp.linalg.norm(ref))


def _compare(label, steps, ref_steps, ref_tokens):
    import jax.numpy as jnp
    import numpy as np
    pre = _rel_err(steps[0], ref_steps[0])
    dec = _rel_err(steps[1], ref_steps[1])
    mx = max(float(jnp.max(jnp.abs(s - r)))
             for s, r in zip(steps[:2], ref_steps[:2]))
    tokens = np.stack([np.asarray(jnp.argmax(s, -1)) for s in steps], 1)
    share = float(np.mean(tokens == ref_tokens))
    print(f"{label}: prefill logits rel-L2 {pre:.3e}, first decode logits "
          f"rel-L2 {dec:.3e} (tolerance {LOGIT_TOL:g}); max |diff| {mx:.3e}")
    print(f"{label}: greedy tokens {tokens.tolist()}; agree with "
          f"reference {share:.3f}")
    check(pre <= LOGIT_TOL and dec <= LOGIT_TOL,
          f"{label}: logits disagree with the reference beyond "
          f"{LOGIT_TOL:g}")


def _logit_check(params, cfg_ref, cfg_pallas, *, interpret: bool):
    import jax.numpy as jnp
    import numpy as np
    ids = jnp.asarray(_check_tokens())
    ref_steps, k_ref = _dense_run(params, cfg_ref, ids, None)
    ref_tokens = np.stack([np.asarray(jnp.argmax(s, -1))
                           for s in ref_steps], 1)
    print(f"reference dense: greedy tokens {ref_tokens.tolist()}; "
          f"tpu_custom_call in prefill/decode: {k_ref}")
    forced = [jnp.asarray(ref_tokens[:, i]) for i in range(CHECK_STEPS)]
    for label, run in (("pallas dense", _dense_run),
                       ("pallas paged", _paged_run)):
        steps, kernels = run(params, cfg_pallas, ids, forced)
        print(f"{label}: tpu_custom_call in prefill/decode: {kernels}")
        if not interpret:
            check(all(kernels), f"{label}: a program holds no Pallas "
                  "kernel (tpu_custom_call)")
        _compare(label, steps, ref_steps, ref_tokens)


# ---------------------------------------------------------------------------
# four chips: mesh-sharded serve vs the single-device engine
# ---------------------------------------------------------------------------


def _four_chips(args, timer):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import minions_tasks
    from repro.models import transformer as T
    from repro.serving import InferenceEngine

    def build():
        from repro.configs import get_config, get_smoke_config
        cfg = (get_smoke_config(ARCH) if args.rehearse else
               get_config(ARCH).replace(num_layers=FOUR_CHIP_LAYERS))
        params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
        return InferenceEngine(cfg, jax.block_until_ready(params),
                               truncate_long=True)

    single = _phase("build engine (random weights)", timer, build)
    cfg = single.cfg
    print(f"config: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model}"
          f" heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}")
    prompts = [t.context + "\n" + t.query for t in minions_tasks(SLOTS)]
    ids = _check_tokens(len(jax.devices()))   # whole rows per data shard

    def forward(params, rows):
        """Prefill + one decode step fed the prefill's argmax; GSPMD
        partitions both from the committed input shardings."""
        logits, cache = jax.jit(
            lambda p, t: T.prefill(p, cfg, {"tokens": t}, 2 * CHECK_LEN))(
                params, rows)
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        dec, _ = jax.jit(lambda p, t, c: T.decode_step(p, cfg, t, c))(
            params, tok, cache)
        return [logits[:, -1], dec[:, -1]]

    def serve(engine, label):
        texts = engine.serve(prompts, max_new_tokens=32, temperature=0.0,
                             slots=SLOTS)
        print(f"{label}: served {len(texts)} rows, "
              f"{engine.usage.decode_tokens} decode tokens")
        return texts

    ref_texts = _phase("single-device serve", timer, serve, single,
                       "single device")
    ref_steps = _phase("single-device logits", timer, forward, single.params,
                       jnp.asarray(ids))
    for mp in (1, 2):
        mesh = make_host_mesh(mp)
        label = f"mesh {dict(mesh.shape)}"
        eng = InferenceEngine(cfg, single.params, mesh=mesh,
                              max_seq_len=single.max_seq_len,
                              truncate_long=True)
        texts = _phase(f"{label} serve", timer, serve, eng, label)
        share = float(np.mean([a == b for a, b in zip(texts, ref_texts)]))
        print(f"{label}: served texts equal to single device: {share:.3f}")
        rows = jax.device_put(jnp.asarray(ids),
                              NamedSharding(mesh, P("data", None)))
        steps = _phase(f"{label} logits", timer, forward, eng.params, rows)
        ref_tokens = np.stack([np.asarray(jnp.argmax(s, -1))
                               for s in ref_steps], 1)
        _compare(label, steps, ref_steps, ref_tokens)
        del eng


if __name__ == "__main__":
    sys.exit(main())
