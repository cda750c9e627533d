"""The one traffic generator: MinionS tasks from a mix's parameters.

A mix file (``bench/traffic/<name>.json``) sets how many tasks each closed-
loop call carries, the query of each of them (``queries``, one per place in
the call), the document size in pages of exactly ``page_bytes`` bytes, the
protocol's knobs, and how many warm-up calls the harness makes before the
window (``warmup_calls``).

Documents come from ``repro.core.tasks.make_task`` with an id derived from
the seed, the stream ("warmup" or "window"), the call and the task's place
in it: every call of every run reads documents it has never seen, and the
warm-up never serves a document of the window.  Each page then opens with
a letter of its own within the call (so two chunks never share more than
the worker template) and is cut, or lengthened with its own sentences, to
``page_bytes`` bytes, and each task asks its place's query.  So every seed
sends the same set of sizes (the same prompt lengths, the same questions
of every chunk, the same shared prefixes) with other words in them, and a
seed changes neither the work nor the engine's shapes.
Every ``GREEDY_EVERY``-th task of a call asks its workers for greedy
answers (temperature 0), the others sample at ``worker_temperature``: the
greedy jobs are the ones the output check can judge token by token.
"""
from __future__ import annotations

import hashlib
from typing import List

GREEDY_EVERY = 2
PAGE_TAGS = "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"


def derive(seed: int, *parts) -> int:
    """A 60-bit integer from the seed and labels (any size of seed)."""
    h = hashlib.sha256("/".join(map(str, (seed,) + parts)).encode())
    return int.from_bytes(h.digest()[:8], "little") >> 4


def fixed_pages(context: str, page_bytes: int, first_tag: int) -> str:
    """The document with every page opened by its letter (``PAGE_TAGS``
    from ``first_tag`` on) and cut, or lengthened with its own sentences
    after the page header, to exactly ``page_bytes`` bytes."""
    from repro.core.chunking import PAGE_SEP

    pages = []
    for i, page in enumerate(context.split(PAGE_SEP)):
        body = page.partition(". ")[2].encode()     # ASCII after the header
        tag = PAGE_TAGS[(first_tag + i) % len(PAGE_TAGS)]
        b = f"{tag} {page}".encode()
        while len(b) < page_bytes:
            b += b" " + body
        pages.append(b[:page_bytes].decode())
    return PAGE_SEP.join(pages)


def call_specs(mix: dict, seed: int, stream: str, call: int) -> List:
    """The ``TaskSpec``s of one ``ProtocolRunner.run`` call."""
    from repro.core.minions import MinionSConfig
    from repro.core.runtime import TaskSpec
    from repro.core.tasks import make_task

    if mix["protocol"] != "minions":
        raise ValueError(f"unknown protocol {mix['protocol']!r}")
    n = mix["concurrent_tasks"]
    queries = mix["queries"]
    if len(queries) != n:
        raise ValueError(f"{len(queries)} queries for {n} concurrent tasks")
    specs = []
    for i, query in enumerate(queries):
        task = make_task(derive(seed, stream, call, i),
                         n_pages=mix["document_pages"])
        greedy = i % GREEDY_EVERY == GREEDY_EVERY - 1
        cfg = MinionSConfig(
            max_rounds=mix["max_rounds"],
            num_tasks_per_round=mix["num_tasks_per_round"],
            num_samples=mix["num_samples"],
            pages_per_chunk=mix["pages_per_chunk"],
            worker_temperature=0.0 if greedy else mix["worker_temperature"],
            worker_max_tokens=mix["worker_max_tokens"])
        specs.append(TaskSpec(
            "minions", fixed_pages(task.context, mix["page_bytes"],
                                   i * mix["document_pages"]),
            query, cfg, task_id=call * n + i))
    return specs
