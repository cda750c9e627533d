"""CPU fixtures for the benchmark's tests: a tiny model and short documents,
so that a whole run of the harness takes seconds.  Nothing here touches a
TPU."""
import random

import pytest

from bench import traffic

# a model a CPU runs in a blink, float32 so the reference agrees to rounding
TINY_DIMS = {"layers": 2, "d_model": 64, "heads": 4, "kv_heads": 1,
             "head_dim": 16, "d_ff": 128, "vocab": 300, "dtype": "float32",
             "page_size": 16, "num_pages": 256, "slots": 4}
TINY_MIX = {"concurrent_tasks": 2, "pages_per_chunk": 1,
            "num_tasks_per_round": 2, "worker_max_tokens": 5,
            "check_served_tokens": 10}


def short_document(seed: int) -> tuple:
    """(context, query): three short pages, one of them holding the fact."""
    rng = random.Random(seed)
    value = round(rng.uniform(10, 900), 1)
    year = rng.choice([2014, 2015, 2016])
    pages = [f"Acme page {i}. Costs rose." for i in range(3)]
    pages[rng.randrange(3)] += (f" The total revenue for fiscal year {year} "
                                f"was ${value} million.")
    return "\f".join(pages), (f"What was the total revenue for FY{year} "
                              "(in millions of USD)?")


@pytest.fixture
def tiny_traffic(monkeypatch):
    """Swap the MinionS documents for short ones (same protocol, same
    runner, same knobs), so a CPU run of the harness is quick."""
    from repro.core.minions import MinionSConfig
    from repro.core.runtime import TaskSpec

    def call_specs(mix, seed, stream, call):
        specs = []
        for i in range(mix["concurrent_tasks"]):
            context, query = short_document(traffic.derive(seed, stream,
                                                           call, i))
            greedy = i % traffic.GREEDY_EVERY == traffic.GREEDY_EVERY - 1
            cfg = MinionSConfig(
                max_rounds=1, num_tasks_per_round=mix["num_tasks_per_round"],
                num_samples=1, pages_per_chunk=mix["pages_per_chunk"],
                worker_temperature=0.0 if greedy else 0.2,
                worker_max_tokens=mix["worker_max_tokens"])
            specs.append(TaskSpec("minions", context, query, cfg,
                                  task_id=call * 10 + i))
        return specs

    monkeypatch.setattr(traffic, "call_specs", call_specs)


# the tiny float32 model's own limit: sound runs read under 1e-5 (rounding
# of two float32 forwards), a planted fault reads 3e-3 or more
TINY_LIMITS = {"max_logit_gap": {"limit": 1e-3}}


@pytest.fixture
def rehearse():
    return {"dims": dict(TINY_DIMS), "mix": dict(TINY_MIX),
            "backend": "reference", "limits": dict(TINY_LIMITS)}
