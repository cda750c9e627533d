"""Pallas kernels vs. pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import chunked_prefill, gqa_decode
from repro.kernels.ref import chunked_prefill_ref, gqa_decode_ref

pytestmark = pytest.mark.slow


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,hd,chunk", [
    (128, 2, 64, 64),
    (256, 4, 64, 100),    # padding path (256 % 128 == 0 but chunk ragged)
    (300, 2, 128, 75),    # sequence padding path
    (512, 1, 32, 512),    # single segment == plain causal
])
def test_chunked_prefill_matches_ref(s, h, hd, chunk, dtype):
    key = jax.random.PRNGKey(s + h)
    ks = jax.random.split(key, 3)
    b = 2
    q = _rand(ks[0], (b, s, h, hd), dtype)
    k = _rand(ks[1], (b, s, h, hd), dtype)
    v = _rand(ks[2], (b, s, h, hd), dtype)
    seg = (jnp.arange(s) // chunk)[None, :].repeat(b, 0).astype(jnp.int32)
    out = chunked_prefill(q, k, v, seg)
    ref = chunked_prefill_ref(q, k, v, seg)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_chunked_prefill_gqa_head_repeat():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    b, s, h, hkv, hd = 1, 128, 8, 2, 64
    q = _rand(ks[0], (b, s, h, hd), jnp.float32)
    k = _rand(ks[1], (b, s, hkv, hd), jnp.float32)
    v = _rand(ks[2], (b, s, hkv, hd), jnp.float32)
    seg = jnp.zeros((b, s), jnp.int32)
    out = chunked_prefill(q, k, v, seg)
    kr = jnp.repeat(k, h // hkv, axis=2)
    vr = jnp.repeat(v, h // hkv, axis=2)
    ref = chunked_prefill_ref(q, kr, vr, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_chunk_isolation_is_exact():
    """Jobs must not attend across chunk boundaries: attention over
    [A;B] with segments == attention over A and B separately."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    b, s, h, hd = 1, 256, 2, 64
    q = _rand(ks[0], (b, s, h, hd), jnp.float32)
    k = _rand(ks[1], (b, s, h, hd), jnp.float32)
    v = _rand(ks[2], (b, s, h, hd), jnp.float32)
    seg = jnp.concatenate([jnp.zeros(128), jnp.ones(128)]).astype(
        jnp.int32)[None]
    joint = chunked_prefill(q, k, v, seg)
    zero = jnp.zeros((b, 128), jnp.int32)
    part_a = chunked_prefill(q[:, :128], k[:, :128], v[:, :128], zero)
    part_b = chunked_prefill(q[:, 128:], k[:, 128:], v[:, 128:], zero)
    np.testing.assert_allclose(np.asarray(joint[:, :128]),
                               np.asarray(part_a), atol=2e-5)
    np.testing.assert_allclose(np.asarray(joint[:, 128:]),
                               np.asarray(part_b), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,hkv,l", [
    (8, 2, 256),
    (8, 8, 512),     # MHA
    (4, 1, 700),     # MQA + padding path
    (16, 4, 1024),
])
def test_gqa_decode_matches_ref(h, hkv, l, dtype):
    key = jax.random.PRNGKey(h * l)
    ks = jax.random.split(key, 3)
    b, hd = 3, 64
    q = _rand(ks[0], (b, h, hd), dtype)
    kc = _rand(ks[1], (b, l, hkv, hd), dtype)
    vc = _rand(ks[2], (b, l, hkv, hd), dtype)
    valid = jnp.array([l, max(1, l // 3), max(1, l // 7)], jnp.int32)
    out = gqa_decode(q, kc, vc, valid)
    ref = gqa_decode_ref(q, kc, vc, valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


def test_gqa_decode_scalar_valid_len_broadcasts():
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    b, h, hkv, hd, l = 2, 4, 2, 32, 256
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kc = _rand(ks[1], (b, l, hkv, hd), jnp.float32)
    vc = _rand(ks[2], (b, l, hkv, hd), jnp.float32)
    out = gqa_decode(q, kc, vc, 100)
    ref = gqa_decode_ref(q, kc, vc, jnp.full((b,), 100, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_gqa_decode_ignores_invalid_slots():
    """Garbage beyond valid_len must not affect the result."""
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    b, h, hkv, hd, l = 1, 4, 2, 32, 256
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kc = _rand(ks[1], (b, l, hkv, hd), jnp.float32)
    vc = _rand(ks[2], (b, l, hkv, hd), jnp.float32)
    out1 = gqa_decode(q, kc, vc, 64)
    kc2 = kc.at[:, 64:].set(1e4)
    vc2 = vc.at[:, 64:].set(-1e4)
    out2 = gqa_decode(q, kc2, vc2, 64)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


@pytest.mark.parametrize("h,hkv,l", [(8, 2, 512), (4, 4, 256), (4, 1, 768)])
def test_gqa_decode_start_offset_matches_ref(h, hkv, l):
    """Per-row [start, valid) windows (left-padded engine rows)."""
    key = jax.random.PRNGKey(h + l)
    ks = jax.random.split(key, 3)
    b, hd = 3, 64
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kc = _rand(ks[1], (b, l, hkv, hd), jnp.float32)
    vc = _rand(ks[2], (b, l, hkv, hd), jnp.float32)
    start = jnp.array([0, l // 4, l // 2 + 7], jnp.int32)
    valid = jnp.array([l, 3 * l // 4, l // 2 + 9], jnp.int32)
    out = gqa_decode(q, kc, vc, valid, start=start)
    ref = gqa_decode_ref(q, kc, vc, valid, start=start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gqa_decode_start_ignores_left_padding_garbage():
    """Garbage before ``start`` (pad slots of a left-padded row) must not
    affect the result — the engine's ragged-batch decode contract."""
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    b, h, hkv, hd, l = 2, 4, 2, 32, 256
    q = _rand(ks[0], (b, h, hd), jnp.float32)
    kc = _rand(ks[1], (b, l, hkv, hd), jnp.float32)
    vc = _rand(ks[2], (b, l, hkv, hd), jnp.float32)
    start = jnp.array([32, 100], jnp.int32)
    valid = jnp.array([200, 256], jnp.int32)
    out1 = gqa_decode(q, kc, vc, valid, start=start)
    kc2 = kc.at[:, :32].set(1e4)
    vc2 = vc.at[:, :32].set(-1e4)
    out2 = gqa_decode(q, kc2, vc2, valid, start=start)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_chunked_prefill_gqa_native_multi_block():
    """GQA without materialised repeat across several q/kv blocks AND
    a chunk boundary (the packed-prefill shape)."""
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    b, s, h, hkv, hd = 2, 384, 8, 2, 64
    q = _rand(ks[0], (b, s, h, hd), jnp.float32)
    k = _rand(ks[1], (b, s, hkv, hd), jnp.float32)
    v = _rand(ks[2], (b, s, hkv, hd), jnp.float32)
    seg = (jnp.arange(s) // 150)[None, :].repeat(b, 0).astype(jnp.int32)
    out = chunked_prefill(q, k, v, seg)
    kr = jnp.repeat(k, h // hkv, axis=2)
    vr = jnp.repeat(v, h // hkv, axis=2)
    ref = chunked_prefill_ref(q, kr, vr, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


# ---------------------------------------------------------------------------
# attention_backend="pallas" wiring: model-level parity with the reference
# ---------------------------------------------------------------------------


def test_model_pallas_backend_matches_reference():
    """prefill + a few decode steps through the model dispatch agree
    between the jnp reference path and the fused Pallas kernels,
    including left-padded rows."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    cfg_ref = ModelConfig(num_layers=2, d_model=128, num_heads=4,
                          num_kv_heads=2, head_dim=32, d_ff=256,
                          vocab_size=512)
    cfg_pal = cfg_ref.replace(attention_backend="pallas")
    params = T.init_params(cfg_ref, jax.random.PRNGKey(0))

    b, s = 2, 64
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, 256)
    segs = jnp.where(jnp.arange(s)[None, :] < 10, -1, 0).astype(jnp.int32)
    batch = {"tokens": toks, "segment_ids": segs}
    lr, cr = T.prefill(params, cfg_ref, batch, capacity=256)
    lp, cp = T.prefill(params, cfg_pal, batch, capacity=256)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=1e-5,
                               rtol=1e-5)
    tok = jnp.argmax(lr[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(2):
        lr, cr = T.decode_step(params, cfg_ref, tok, cr)
        lp, cp = T.decode_step(params, cfg_pal, tok, cp)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                                   atol=1e-5, rtol=1e-5)
        tok = jnp.argmax(lr[:, -1], -1).astype(jnp.int32)[:, None]


def test_pallas_decode_noncontiguous_mask_falls_back():
    """A slot_mask with a hole (no single [start, pos] window) must still
    be honored — the pallas branch detects it on device and uses the
    reference path instead of attending to masked slots."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    cfg_ref = ModelConfig(num_layers=1, d_model=64, num_heads=4,
                          num_kv_heads=2, head_dim=16, d_ff=128,
                          vocab_size=512)
    cfg_pal = cfg_ref.replace(attention_backend="pallas")
    params = T.init_params(cfg_ref, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)
    _, cache = T.prefill(params, cfg_ref, {"tokens": toks}, capacity=64)
    # punch a hole in the valid region
    cache["slot_mask"] = cache["slot_mask"].at[:, 10:15].set(False)
    tok = jnp.array([[65]], jnp.int32)
    lr, _ = T.decode_step(params, cfg_ref, tok, dict(cache))
    lp, new_cache = T.decode_step(params, cfg_pal, tok, dict(cache))
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=1e-5,
                               rtol=1e-5)
    # the step is counted as a fallback; the reference backend never is
    assert int(T.decode_falls_back(cfg_pal, cache, new_cache)) == 1
    assert int(T.decode_falls_back(cfg_ref, cache, new_cache)) == 0


def test_pallas_backend_falls_back_on_sliding_window():
    """Sliding-window configs must silently use the reference path (the
    kernels cover full causal attention only)."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig

    cfg_win = ModelConfig(num_layers=1, d_model=64, num_heads=2,
                          num_kv_heads=2, head_dim=32, d_ff=128,
                          vocab_size=512, sliding_window=16)
    cfg_pal = cfg_win.replace(attention_backend="pallas")
    params = T.init_params(cfg_win, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)
    lr, _ = T.prefill(params, cfg_win, {"tokens": toks}, capacity=64)
    lp, _ = T.prefill(params, cfg_pal, {"tokens": toks}, capacity=64)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp), atol=1e-6)
