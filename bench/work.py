"""The work a window's traffic requires, counted from the traffic alone.

Whatever the program does (padding, buckets, prefixes computed twice), the
count is the least any exact implementation has to do, so a share of a peak
built on it cannot read above what the chip did:

- each distinct prompt token counts once, by a token trie over the prompts
  of one closed-loop call (calls run one after another, so only one call's
  prompts can share work);
- each served token but a job's last is fed through one decode forward, and
  a logit row is needed for each job's last prompt token and each of those
  forwards;
- attention counts each row's causal context;
- decode attention reads a K/V token once per decode step for all the jobs
  of a call that are still decoding and whose prompts share it.

What a token costs in FLOPs and bytes is the architecture's: its module
(``bench/arch/<model_type>.py``) turns these counts into ``step_flops``,
``prefill_attn_work`` and ``decode_attn_work``.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Job:
    """One served worker job: its prompt ids, how many tokens it served,
    and the closed-loop call it belonged to."""
    prompt: Tuple[int, ...]
    served: int
    call: int = 0


def _lcp(a: Sequence[int], b: Sequence[int]) -> int:
    n = min(len(a), len(b))
    i = 0
    while i < n and a[i] == b[i]:
        i += 1
    return i


def trie(prompts: Iterable[Sequence[int]]) -> Tuple[int, int]:
    """(distinct tokens, sum of their causal contexts) of a token trie over
    ``prompts``.  A trie node at position p (0-based) attends p + 1 keys.
    Sorted prompts add exactly the nodes past their common prefix with
    the previous one."""
    nodes = ctx = 0
    prev: Sequence[int] = ()
    for p in sorted(tuple(p) for p in prompts):
        lo, hi = _lcp(p, prev), len(p)
        nodes += hi - lo
        ctx += (hi * (hi + 1) - lo * (lo + 1)) // 2
        prev = p
    return nodes, ctx


def _by_call(jobs: Sequence[Job]) -> List[List[Job]]:
    calls = {}
    for j in jobs:
        calls.setdefault(j.call, []).append(j)
    return [calls[c] for c in sorted(calls)]


@dataclasses.dataclass(frozen=True)
class Counts:
    """Token counts behind every work function."""
    prompt_tokens: int        # distinct prompt tokens (trie nodes)
    prompt_ctx: int           # sum of their causal contexts
    decode_forwards: int      # served tokens fed back, sum of (served - 1)
    decode_ctx: int           # sum of their causal contexts
    logit_rows: int           # jobs + decode forwards
    decode_kv_reads: int      # K/V tokens read, once per step per share


def counts(jobs: Sequence[Job]) -> Counts:
    prompt_tokens = prompt_ctx = fwd = dctx = rows = kv = 0
    for call in _by_call(jobs):
        n, c = trie(j.prompt for j in call)
        prompt_tokens += n
        prompt_ctx += c
        for j in call:
            steps = max(j.served - 1, 0)
            L = len(j.prompt)
            fwd += steps
            # forward k (1..steps) feeds served token k at position L+k-1
            dctx += steps * L + steps * (steps + 1) // 2
            rows += 1 + steps
            # its own served tokens already in the cache at step k: k
            kv += steps * (steps + 1) // 2
        # shared prompt K/V, once per step over the jobs still decoding
        # (steps 1..served-1); the live set changes only where a job ends
        done = 0
        for end in sorted({j.served - 1 for j in call if j.served > 1}):
            live = [j.prompt for j in call if j.served - 1 >= end]
            kv += (end - done) * trie(live)[0]
            done = end
    return Counts(prompt_tokens, prompt_ctx, fwd, dctx, rows, kv)


def roofline_seconds(flops: int, byts: int, peak) -> Tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = byts / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
