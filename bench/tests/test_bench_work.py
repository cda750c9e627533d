"""The work functions against counts made by hand at small shapes."""
import dataclasses
import types

import pytest

from bench import work
from bench.spec import Spec

LLAMA = Spec().arch_module("llama")
DIMS = LLAMA.Dims(name="t", layers=2, d_model=8, heads=4, kv_heads=2,
                  head_dim=2, d_ff=16, vocab=10, rope_theta=1e4,
                  norm_eps=1e-5, dtype="bfloat16", page_size=4, num_pages=8,
                  slots=2, max_seq_len=64)


def _ctx(counts):
    """What the work functions read of a metric ``Context``."""
    return types.SimpleNamespace(dims=DIMS, counts=counts)


def brute_trie(prompts):
    nodes = {p[:i + 1] for p in map(tuple, prompts) for i in range(len(p))}
    return len(nodes), sum(len(n) for n in nodes)


@pytest.mark.parametrize("prompts", [
    [(1, 2, 3)],
    [(1, 2, 3), (1, 2, 4)],
    [(1, 2, 3), (1, 2, 3)],                  # a whole prompt shared
    [(5,), (1, 2), (1, 2, 3, 4), (1, 9)],
    [(3, 1), (1, 2, 3), (3, 1, 4), ()],
])
def test_trie_counts_each_distinct_token_once(prompts):
    assert work.trie(prompts) == brute_trie(prompts)


def test_trie_by_hand():
    # a b c / a b d: nodes a, ab, abc, abd; contexts 1 + 2 + 3 + 3
    assert work.trie([(1, 2, 3), (1, 2, 4)]) == (4, 9)


def test_counts_by_hand():
    # one call: two jobs share a 3-token prefix; 3 and 2 served tokens
    jobs = [work.Job((1, 2, 3, 7), 3), work.Job((1, 2, 3, 8, 9), 2)]
    c = work.counts(jobs)
    assert c.prompt_tokens == 6                   # 1 2 3 7 8 9
    assert c.prompt_ctx == 1 + 2 + 3 + 4 + 4 + 5
    assert c.decode_forwards == 2 + 1             # served - 1 each
    # job A feeds its tokens at positions 4, 5 (contexts 5, 6); job B at 5
    assert c.decode_ctx == 5 + 6 + 6
    assert c.logit_rows == 3 + 2
    # step 1: both decode, shared prompt K/V = the 6 trie nodes, own
    # served tokens 1 + 1; step 2: only A, its 4 prompt tokens + 2 own
    assert c.decode_kv_reads == (6 + 1 + 1) + (4 + 2)


def test_calls_do_not_share_work():
    a = work.Job((1, 2, 3), 1, call=0)
    b = work.Job((1, 2, 3), 1, call=1)
    assert work.counts([a, b]).prompt_tokens == 6
    assert work.counts([a, dataclasses.replace(b, call=0)]).prompt_tokens == 3


def test_flops_by_hand():
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8, gate/up/down 3*8*16 = 576
    assert LLAMA.matmul_flops_per_token(DIMS) == 2 * 576 * 2
    assert LLAMA.lm_head_flops(DIMS) == 2 * 8 * 10
    assert LLAMA.attn_flops_per_key(DIMS) == 4 * 4 * 2 * 2
    c = work.Counts(prompt_tokens=6, prompt_ctx=19, decode_forwards=3,
                    decode_ctx=17, logit_rows=5, decode_kv_reads=14)
    assert LLAMA.step_flops(_ctx(c)) == (2304 * 9 + 64 * (19 + 17) + 160 * 5)


def test_attention_work_by_hand():
    c = work.Counts(prompt_tokens=6, prompt_ctx=19, decode_forwards=3,
                    decode_ctx=17, logit_rows=5, decode_kv_reads=14)
    # bytes per element 2, head_dim 2, 2 layers -> 8 per head-row
    assert LLAMA.prefill_attn_work(_ctx(c)) == (64 * 19, 6 * 8 * (8 + 4))
    assert LLAMA.decode_attn_work(_ctx(c)) == (64 * 17,
                                              14 * 2 * 2 * 8 + 3 * 2 * 4 * 8)


def test_shared_kv_read_once_per_step():
    """Rows that share a prompt read it once a step: four jobs on one
    10-token prompt, 3 served each, read 10 + own tokens per step."""
    jobs = [work.Job(tuple(range(10)), 3) for _ in range(4)]
    c = work.counts(jobs)
    assert c.decode_kv_reads == (10 + 4 * 1) + (10 + 4 * 2)


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_seconds(1000, 50, peak) == (10.0, "flops")
    assert work.roofline_seconds(100, 500, peak) == (50.0, "bytes")
