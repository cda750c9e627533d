"""Paged KV cache: page pool, radix prefix index, paged kernels, engine
prefix reuse.

Property tests run through tests/_hypothesis_compat.py (hypothesis when
installed, seeded-sampling fallback otherwise) and pin the allocator's
invariants: refcounts never go negative, no page leaks across arbitrary
alloc/retain/release interleavings, radix insert/match/evict round-trips
keep pool accounting exact, and copy-on-write preserves the copied
prefix bytes bit-for-bit.  The engine-level tests assert the counters
(cumulative + reset) and that a repeated serve call actually reuses
cached prefix pages (fewer prefill tokens, identical text).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_smoke_config
from repro.kernels import ref as kernels_ref
from repro.kernels.ops import paged_gqa_decode, paged_prefill
from repro.models import transformer as T
from repro.serving import EngineUsage, InferenceEngine, PagePool, RadixIndex
from repro.serving.paging import NULL_PAGE, cow_copy


# ---------------------------------------------------------------------------
# PagePool invariants
# ---------------------------------------------------------------------------


def test_pool_basic_alloc_release():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.available == 7            # page 0 reserved
    pages = pool.alloc(3)
    assert len(set(pages)) == 3 and NULL_PAGE not in pages
    assert pool.available == 4
    for p in pages:
        assert pool.refcount(p) == 1
        pool.release(p)
    assert pool.available == 7


def test_pool_exhaustion_and_unowned_release():
    pool = PagePool(num_pages=4, page_size=4)
    pool.alloc(3)
    with pytest.raises(RuntimeError):
        pool.alloc(1)
    with pytest.raises(ValueError):
        pool.release(NULL_PAGE)           # null page is never released
    free = PagePool(num_pages=4, page_size=4)
    with pytest.raises(ValueError):
        free.release(2)                   # never allocated


@settings(max_examples=40)
@given(st.lists(st.sampled_from(["alloc", "retain", "release"]),
                min_size=1, max_size=60),
       st.integers(min_value=2, max_value=12))
def test_pool_refcount_never_negative_no_leak(ops, num_pages):
    """Arbitrary alloc/retain/release interleavings: refcounts stay >= 0,
    available + live always equals num_pages - 1, and releasing every
    owned ref drains back to a full pool (no leaked page)."""
    pool = PagePool(num_pages=num_pages, page_size=4)
    owned = []                            # one entry per outstanding ref
    for i, op in enumerate(ops):
        if op == "alloc":
            try:
                owned += pool.alloc(1)
            except RuntimeError:
                assert pool.available == 0
        elif op == "retain" and owned:
            p = owned[i % len(owned)]
            pool.retain(p)
            owned.append(p)
        elif op == "release" and owned:
            pool.release(owned.pop(i % len(owned)))
        live = {p for p in owned}
        for p in live:
            assert pool.refcount(p) == owned.count(p)
        assert pool.available == num_pages - 1 - len(live)
    for p in owned:
        pool.release(p)
    assert pool.available == num_pages - 1


# ---------------------------------------------------------------------------
# RadixIndex invariants
# ---------------------------------------------------------------------------


def _naive_lcp(a, b):
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


def _naive_lcp_pages(inserted, tokens, ps):
    """Oracle: longest common full-chunk prefix against every inserted
    prompt, in pages."""
    return max((_naive_lcp(toks, tokens) for toks in inserted),
               default=0) // ps


@settings(max_examples=30)
@given(st.lists(st.lists(st.integers(min_value=0, max_value=3),
                         min_size=4, max_size=24),
                min_size=1, max_size=8),
       st.lists(st.integers(min_value=0, max_value=3),
                min_size=0, max_size=24))
def test_radix_longest_prefix_match(prompts, probe):
    """match() returns exactly the longest inserted full-page prefix of
    the probe (with the pages that were inserted for it), plus an
    optional trailing token-level partial — the COW source."""
    ps = 4
    probe = tuple(probe)
    pool = PagePool(num_pages=256, page_size=ps)
    radix = RadixIndex(page_size=ps)
    page_of = {}                          # full-chunk prefix -> page id
    inserted = []
    for toks in prompts:
        toks = tuple(toks)
        n_full = len(toks) // ps
        pages = pool.alloc(n_full)
        radix.insert(toks, pages, pool)
        inserted.append(toks)
        for k in range(n_full):
            # dedup: the radix keeps the FIRST page for a repeated chunk
            page_of.setdefault(toks[:(k + 1) * ps], pages[k])
    pages, fills = radix.match(probe)
    assert len(pages) == len(fills)
    full = len(pages)
    if fills and fills[-1] < ps:
        full -= 1
    assert all(f == ps for f in fills[:full])
    expect = _naive_lcp_pages(inserted, probe, ps)
    assert full == expect
    for k in range(full):
        assert pages[k] == page_of[probe[:(k + 1) * ps]]
    # trailing partial: the best token-level divergence among the chunks
    # that extend the matched full prefix
    partial = max((_naive_lcp(toks[full * ps:(full + 1) * ps],
                              probe[full * ps:(full + 1) * ps])
                   for toks in inserted
                   if len(toks) >= (full + 1) * ps
                   and toks[:full * ps] == probe[:full * ps]), default=0)
    if full < len(pages):
        assert fills[-1] == partial and 0 < partial < ps
    else:
        assert partial == 0


@settings(max_examples=25)
@given(st.lists(st.lists(st.integers(min_value=0, max_value=2),
                         min_size=4, max_size=20),
                min_size=1, max_size=6))
def test_radix_insert_evict_round_trip(prompts):
    """Inserting then evicting everything returns the pool to full and
    the index to empty; refcounts account for exactly one radix ref per
    indexed node."""
    ps = 4
    pool = PagePool(num_pages=128, page_size=ps)
    radix = RadixIndex(page_size=ps)
    for toks in prompts:
        toks = tuple(toks)
        pages = pool.alloc(len(toks) // ps)
        created = radix.insert(toks, pages, pool)
        # insert retains the pages it newly indexes; the caller's refs
        # are still owed — release them so the radix holds the only ref
        for p in pages:
            pool.release(p)
        assert created <= len(pages)
    n_indexed = len(radix)
    assert pool.available == 127 - n_indexed
    freed = radix.evict(pool, 127)        # demand the whole pool back
    assert freed == n_indexed
    assert len(radix) == 0
    assert pool.available == 127


def test_radix_audit_reconciles_with_pool():
    """The REPRO_SANITIZE audit passes across insert/evict churn: node
    counts, child keys, parent backlinks, and per-page pool refs all
    reconcile at every step."""
    ps = 4
    pool = PagePool(num_pages=64, page_size=ps)
    radix = RadixIndex(page_size=ps)
    prompts = [tuple(range(i, i + 12)) for i in range(0, 24, 4)]
    for toks in prompts:
        pages = pool.alloc(len(toks) // ps)
        radix.insert(toks, pages, pool)
        for p in pages:
            pool.release(p)
        radix.audit(pool)
        pool.audit()
    radix.evict(pool, pool.available + 2)
    radix.audit(pool)
    pool.audit()


def test_radix_audit_catches_corruption():
    """Break each audited invariant by hand; the audit must name it."""
    ps = 4
    pool = PagePool(num_pages=16, page_size=ps)
    radix = RadixIndex(page_size=ps)
    toks = (1, 2, 3, 4, 5, 6, 7, 8)
    pages = pool.alloc(2)
    radix.insert(toks, pages, pool)
    for p in pages:
        pool.release(p)
    radix.audit(pool)                      # sanity: starts consistent

    # (a) dangling page: the pool no longer holds what the trie indexes
    node = radix.root.children[(1, 2, 3, 4)]
    pool.release(node.page)
    with pytest.raises(AssertionError, match="dangling page"):
        radix.audit(pool)
    assert pool.alloc(1) == [node.page]    # free stack hands it back
    radix.audit(pool)

    # (b) node-count drift
    radix.n_nodes += 1
    with pytest.raises(AssertionError, match="n_nodes"):
        radix.audit(pool)
    radix.n_nodes -= 1

    # (c) a child keyed under the wrong chunk
    child = node.children.pop((5, 6, 7, 8))
    node.children[(9, 9, 9, 9)] = child
    with pytest.raises(AssertionError, match="child keyed"):
        radix.audit(pool)
    node.children.pop((9, 9, 9, 9))
    node.children[(5, 6, 7, 8)] = child

    # (d) two nodes indexing one page
    child.page = node.page
    with pytest.raises(AssertionError, match="indexed by two"):
        radix.audit(pool)


def test_radix_partial_page_fill_from_match():
    """A probe diverging mid-page reports the partial divergence page
    with its token fill (the COW source)."""
    ps = 4
    pool = PagePool(num_pages=16, page_size=ps)
    radix = RadixIndex(page_size=ps)
    toks = (1, 2, 3, 4, 5, 6)             # 1 full page + 2 spare tokens
    pages = pool.alloc(1)
    radix.insert(toks, pages, pool)
    hit, fills = radix.match((1, 2, 3, 4, 9, 9))
    assert list(hit) == list(pages) and list(fills) == [ps]
    hit, fills = radix.match((1, 2, 3, 9))
    assert list(hit) == list(pages) and list(fills) == [3]


# ---------------------------------------------------------------------------
# copy-on-write
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=0, max_value=4))
def test_cow_preserves_prefix_bytes(fill):
    ps, hkv, hd = 4, 2, 8
    key = jax.random.PRNGKey(fill)
    pool = jax.random.normal(key, (6, ps, hkv, hd), jnp.float32)
    out = cow_copy(pool, jnp.asarray([2]), jnp.asarray([5]),
                   jnp.asarray([fill]))
    np.testing.assert_array_equal(np.asarray(out[5, :fill]),
                                  np.asarray(pool[2, :fill]))
    assert not np.asarray(out[5, fill:]).any()      # rest zeroed
    np.testing.assert_array_equal(np.asarray(out[:5]),
                                  np.asarray(pool[:5]))  # others untouched


# ---------------------------------------------------------------------------
# paged kernel parity vs the dense-gather oracle
# ---------------------------------------------------------------------------


def _pool_fixture(seed=0, b=3, n_pages=10, ps=8, hkv=2, group=2, hd=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    kp = jax.random.normal(ks[0], (n_pages, ps, hkv, hd), jnp.float32)
    vp = jax.random.normal(ks[1], (n_pages, ps, hkv, hd), jnp.float32)
    pt = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    valid = jnp.asarray([21, 13, 5], jnp.int32)
    q = jax.random.normal(ks[2], (b, hkv * group, hd), jnp.float32)
    return q, kp, vp, pt, valid


def test_paged_decode_kernel_matches_ref():
    q, kp, vp, pt, valid = _pool_fixture()
    out = paged_gqa_decode(q, kp, vp, pt, valid)
    ref = kernels_ref.paged_gqa_decode_ref(q, kp, vp, pt, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_prefill_kernel_matches_ref():
    q, kp, vp, pt, valid = _pool_fixture()
    b, s = pt.shape[0], 6
    qs = jax.random.normal(jax.random.PRNGKey(9),
                           (b, s, q.shape[1], q.shape[2]), jnp.float32)
    positions = (valid[:, None] - s + jnp.arange(s)[None, :]).clip(0)
    out = paged_prefill(qs, kp, vp, pt, positions, block_q=8)
    ref = kernels_ref.paged_prefill_ref(qs, kp, vp, pt, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# engine: counters + cross-call prefix reuse
# ---------------------------------------------------------------------------


def test_usage_counters_cumulative_and_reset():
    """The paged counters accumulate like every other EngineUsage field
    and reset() zeroes them (regression: new fields must ride the
    dataclass-fields iteration, not a hand-written list)."""
    u = EngineUsage()
    for field in ("pages_allocated", "pages_reused", "prefix_hit_tokens",
                  "prefill_tokens_saved", "cache_hbm_bytes"):
        assert getattr(u, field) == 0
        setattr(u, field, getattr(u, field) + 7)
        setattr(u, field, getattr(u, field) + 5)
        assert getattr(u, field) == 12
    u.reset()
    for field in ("pages_allocated", "pages_reused", "prefix_hit_tokens",
                  "prefill_tokens_saved", "cache_hbm_bytes"):
        assert getattr(u, field) == 0


@pytest.fixture(scope="module")
def paged_engine():
    cfg = get_smoke_config("llama3.2-1b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, max_seq_len=512, paged=True,
                           page_size=16, num_pages=256)


@pytest.fixture(scope="module")
def dense_engine(paged_engine):
    return InferenceEngine(paged_engine.cfg, paged_engine.params,
                           max_seq_len=512)


SHARED = "Extract the revenue figure from this chunk: "
JOBS = [SHARED + s for s in ("alpha beta gamma.", "delta epsilon.",
                             "alpha beta gamma.", "zeta eta theta iota.")]


def test_paged_matches_dense_and_reuses_prefix(paged_engine, dense_engine):
    key = jax.random.PRNGKey(3)
    ref = dense_engine.generate_batch(JOBS, max_new_tokens=16,
                                      temperature=0.0, key=key)
    out = paged_engine.generate_batch(JOBS, max_new_tokens=16,
                                      temperature=0.0, key=key)
    assert out == ref
    first = paged_engine.usage.prefill_tokens
    assert paged_engine.usage.pages_allocated > 0
    # intra-batch sharing: the common instruction prefix prefills once
    assert paged_engine.usage.prefill_tokens_saved > 0

    out2 = paged_engine.generate_batch(JOBS, max_new_tokens=16,
                                       temperature=0.0, key=key)
    assert out2 == ref
    again = paged_engine.usage.prefill_tokens - first
    assert again < first                  # radix served the cached pages
    assert paged_engine.usage.prefix_hit_tokens > 0
    assert paged_engine.usage.pages_reused > 0
    assert paged_engine.usage.cache_hbm_bytes > 0


def test_paged_serve_matches_dense_serve(paged_engine, dense_engine):
    key = jax.random.PRNGKey(5)
    kw = dict(max_new_tokens=[12, 12, 12, 12], temperature=0.0, key=key,
              slots=2)
    assert paged_engine.serve(JOBS, **kw) == dense_engine.serve(JOBS, **kw)


def test_paged_eviction_under_tiny_pool(dense_engine):
    """A pool far smaller than the working set forces LRU eviction every
    call; outputs must stay identical to dense."""
    eng = InferenceEngine(dense_engine.cfg, dense_engine.params,
                          max_seq_len=512, paged=True, page_size=16,
                          num_pages=24)
    key = jax.random.PRNGKey(1)
    for i in range(3):
        p = [f"evict round {i}: " + "x" * (20 + 13 * i)]
        assert eng.generate_batch(p, max_new_tokens=8, temperature=0.0,
                                  key=key) == \
            dense_engine.generate_batch(p, max_new_tokens=8,
                                        temperature=0.0, key=key)


def test_paged_rejects_unsupported_config(dense_engine):
    cfg = dense_engine.cfg.replace(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, dense_engine.params, max_seq_len=512,
                        paged=True)
