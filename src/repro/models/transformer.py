"""Composable decoder (and encoder-decoder) language model.

One ``init_params`` / ``forward`` / ``init_cache`` / ``decode_step`` family
covers every assigned architecture: dense GQA, MoE, xLSTM (SSM), Hymba
hybrid, cross-attention VLM decoders and Whisper-style encoder-decoders.
Pure functions over explicit pytrees: jit/pjit/shard_map friendly.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import kernels as pallas_kernels
from repro.kernels import ref as kernels_ref

from . import moe as moe_lib
from . import ssm as ssm_lib
from .config import ModelConfig
from .layers import (apply_rope, attention, decode_attention,
                     decode_attention_grouped, dense_init,
                     embed_init, head_rms_norm, init_attention, init_gelu_mlp,
                     init_swiglu, gelu_mlp, qkv_project, repeat_kv, rms_norm,
                     swiglu)

Params = Dict[str, Any]
Cache = Dict[str, Any]


def _pallas_attention_ok(cfg: ModelConfig) -> bool:
    """Whether self-attention may dispatch to the fused Pallas kernels.

    ``attention_backend="pallas"`` routes prefill to kernels.chunked_prefill
    (block-diagonal flash attention, native GQA) and decode to
    kernels.gqa_decode (grouped heads, no repeat_kv).  The kernels cover
    full causal attention only, so sliding-window configs fall back to the
    jnp reference path; the kernels define no VJP, so training configs must
    keep the default "reference" backend.
    """
    return cfg.attention_backend == "pallas" and not cfg.sliding_window


# ===========================================================================
# initialisation
# ===========================================================================


def _init_layer(key, cfg: ModelConfig, kind: str) -> Params:
    dtype = cfg.activation_dtype
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": jnp.ones((d,), dtype)}

    if kind in ("attn", "cross"):
        p["attn"] = init_attention(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                                   hd, dtype, qkv_bias=cfg.qkv_bias)
        if kind == "cross":
            p["xattn_gate"] = jnp.zeros((), jnp.float32)
    elif kind == "hybrid":
        p["attn"] = init_attention(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                                   hd, dtype, qkv_bias=cfg.qkv_bias)
        p["mamba"] = ssm_lib.init_mamba(ks[1], d, cfg.num_heads * hd,
                                        cfg.ssm_state, dtype)
        p["w_fuse"] = dense_init(ks[2], cfg.num_heads * hd, d, dtype)
    elif kind == "mlstm":
        p["mlstm"] = ssm_lib.init_mlstm(ks[0], d, cfg.num_heads,
                                        cfg.ssm_proj_factor, dtype)
    elif kind == "slstm":
        p["slstm"] = ssm_lib.init_slstm(ks[0], d, cfg.num_heads, dtype)
    else:
        raise ValueError(kind)

    if cfg.is_encdec and kind == "attn":
        # whisper decoder layers carry an extra cross-attention sub-layer
        p["xnorm"] = jnp.ones((d,), dtype)
        p["xattn"] = init_attention(ks[3], d, cfg.num_heads, cfg.num_kv_heads,
                                    hd, dtype)

    if cfg.d_ff:
        p["norm2"] = jnp.ones((d,), dtype)
        if cfg.is_moe:
            p["moe"] = moe_lib.init_moe(ks[4], d, cfg.d_ff, cfg.num_experts,
                                        dtype)
        elif cfg.family == "audio":
            p["mlp"] = init_gelu_mlp(ks[4], d, cfg.d_ff, dtype)
        else:
            p["mlp"] = init_swiglu(ks[4], d, cfg.d_ff, dtype)
    return p


def _init_encoder_layer(key, cfg: ModelConfig) -> Params:
    dtype = cfg.activation_dtype
    d, hd = cfg.d_model, cfg.resolved_head_dim
    k1, k2 = jax.random.split(key)
    return {
        "norm1": jnp.ones((d,), dtype),
        "attn": init_attention(k1, d, cfg.num_heads, cfg.num_kv_heads, hd,
                               dtype),
        "norm2": jnp.ones((d,), dtype),
        "mlp": init_gelu_mlp(k2, d, cfg.d_ff, dtype),
    }


def stack_layers(layers, cfg: ModelConfig):
    """[L layer-dicts] -> [p stacked trees] with leading (L/p) unit dim —
    the parameter layout consumed by the scan-over-layers path."""
    p = cfg.scan_period()
    units = cfg.num_layers // p
    return [jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[layers[u * p + j] for u in range(units)])
            for j in range(p)]


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = cfg.activation_dtype
    keys = jax.random.split(key, cfg.num_layers + cfg.encoder_layers + 3)
    layer_list = [
        _init_layer(keys[1 + i], cfg, cfg.layer_kind(i))
        for i in range(cfg.num_layers)
    ]
    params: Params = {
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "layers": (stack_layers(layer_list, cfg) if cfg.scan_layers
                   else layer_list),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[-1], cfg.d_model, cfg.vocab_size,
                                       dtype)
    if cfg.is_encdec:
        params["encoder"] = [
            _init_encoder_layer(keys[1 + cfg.num_layers + i], cfg)
            for i in range(cfg.encoder_layers)
        ]
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
    return params


# ===========================================================================
# forward (train / prefill)
# ===========================================================================


def _self_attention(lp: Params, cfg: ModelConfig, x, positions, segment_ids):
    hd = cfg.resolved_head_dim
    q, k, v = qkv_project(lp["attn"], x, cfg.num_heads, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    b, s = x.shape[:2]
    if _pallas_attention_ok(cfg):
        seg = (segment_ids if segment_ids is not None
               else jnp.zeros((b, s), jnp.int32))
        out = pallas_kernels.chunked_prefill(q, k, v, seg)
    else:
        kr = repeat_kv(k, cfg.q_per_kv)
        vr = repeat_kv(v, cfg.q_per_kv)
        out = attention(q, kr, vr, causal=True, window=cfg.sliding_window,
                        segment_ids=segment_ids)
    out = out.reshape(b, s, cfg.num_heads * hd) @ lp["attn"]["wo"]
    return out, (k, v)


def _cross_attention(attn_p: Params, cfg: ModelConfig, x, memory):
    hd = cfg.resolved_head_dim
    b, s, _ = x.shape
    q = (x @ attn_p["wq"]).reshape(b, s, cfg.num_heads, hd)
    mk = (memory @ attn_p["wk"]).reshape(b, -1, cfg.num_kv_heads, hd)
    mv = (memory @ attn_p["wv"]).reshape(b, -1, cfg.num_kv_heads, hd)
    out = attention(q, repeat_kv(mk, cfg.q_per_kv), repeat_kv(mv, cfg.q_per_kv),
                    causal=False)
    return out.reshape(b, s, cfg.num_heads * hd) @ attn_p["wo"], (mk, mv)


def _ffn(lp: Params, cfg: ModelConfig, x, aux_sink=None):
    h = rms_norm(x, lp["norm2"], cfg.norm_eps)
    if cfg.is_moe:
        if aux_sink is not None:
            out, aux = moe_lib.moe_ffn(
                lp["moe"], h, num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=cfg.expert_capacity_factor, return_aux=True)
            aux_sink.append(aux)
            return out
        return moe_lib.moe_ffn(lp["moe"], h, num_experts=cfg.num_experts,
                               top_k=cfg.num_experts_per_tok,
                               capacity_factor=cfg.expert_capacity_factor)
    if cfg.family == "audio":
        return gelu_mlp(lp["mlp"], h)
    return swiglu(lp["mlp"], h)


def run_encoder(params: Params, cfg: ModelConfig, enc_embeddings):
    """Whisper encoder over (stubbed) conv/mel frame embeddings."""
    x = enc_embeddings
    positions = jnp.arange(x.shape[1])[None, :]
    for lp in params["encoder"]:
        h = rms_norm(x, lp["norm1"], cfg.norm_eps)
        hd = cfg.resolved_head_dim
        q, k, v = qkv_project(lp["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                              hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        o = attention(q, repeat_kv(k, cfg.q_per_kv),
                      repeat_kv(v, cfg.q_per_kv), causal=False)
        b, s = h.shape[:2]
        x = x + o.reshape(b, s, cfg.num_heads * hd) @ lp["attn"]["wo"]
        x = x + gelu_mlp(lp["mlp"], rms_norm(x, lp["norm2"], cfg.norm_eps))
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            *, return_aux: bool = False) -> jnp.ndarray:
    """Full-sequence forward: (B, S) tokens -> (B, S, V) logits."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    segment_ids = batch.get("segment_ids")
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    memory = None
    if cfg.is_encdec:
        memory = run_encoder(params, cfg, batch["enc_embeddings"])
    elif cfg.family == "vlm":
        memory = batch.get("image_embeddings")

    x = jnp.take(params["embed"], tokens, axis=0)
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.scan_layers:
        p = cfg.scan_period()

        def unit(carry, unit_params):
            xc, auxc = carry
            for j in range(p):
                xc, a = _decoder_layer(unit_params[j], xc, cfg,
                                       cfg.layer_kind(j), positions,
                                       segment_ids, memory)
                auxc = auxc + a
            return (xc, auxc), None

        body = (jax.checkpoint(
            unit, policy=jax.checkpoint_policies.nothing_saveable)
            if cfg.remat else unit)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total),
                                         tuple(params["layers"]))
    else:
        for i, lp in enumerate(params["layers"]):
            kind = cfg.layer_kind(i)
            layer_fn = _decoder_layer
            if cfg.remat:
                layer_fn = jax.checkpoint(
                    _decoder_layer, static_argnums=(2, 3),
                    policy=jax.checkpoint_policies.nothing_saveable)
            x, aux = layer_fn(lp, x, cfg, kind, positions, segment_ids,
                              memory)
            aux_total = aux_total + aux

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x)
    if return_aux:
        n_moe = sum(cfg.d_ff > 0 and cfg.is_moe
                    for _ in range(cfg.num_layers))
        aux_mean = aux_total / max(n_moe, 1)
        return logits, aux_mean
    return logits


def _decoder_layer(lp: Params, x, cfg: ModelConfig, kind: str, positions,
                   segment_ids, memory):
    """One decoder block (pure, remat-able).  Returns (x, moe_aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind == "attn":
        out, _ = _self_attention(lp, cfg, h, positions, segment_ids)
        x = x + out
        if cfg.is_encdec:
            hx = rms_norm(x, lp["xnorm"], cfg.norm_eps)
            xo, _ = _cross_attention(lp["xattn"], cfg, hx, memory)
            x = x + xo
    elif kind == "cross":
        if memory is None:
            raise ValueError("vlm forward requires image_embeddings")
        out, _ = _cross_attention(lp["attn"], cfg, h, memory)
        x = x + jnp.tanh(lp["xattn_gate"]).astype(x.dtype) * out
    elif kind == "hybrid":
        out, _ = _hybrid_forward(lp, cfg, h, positions, segment_ids)
        x = x + out
    elif kind == "mlstm":
        out, _ = ssm_lib.mlstm_block(lp["mlstm"], h, num_heads=cfg.num_heads,
                                     segment_ids=segment_ids)
        x = x + out
    elif kind == "slstm":
        out, _ = ssm_lib.slstm_block(lp["slstm"], h, num_heads=cfg.num_heads,
                                     segment_ids=segment_ids)
        x = x + out
    if cfg.d_ff:
        if cfg.is_moe:
            hn = rms_norm(x, lp["norm2"], cfg.norm_eps)
            out, aux = moe_lib.moe_ffn(
                lp["moe"], hn, num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok,
                capacity_factor=cfg.expert_capacity_factor, return_aux=True)
            x = x + out
        else:
            x = x + _ffn(lp, cfg, x)
    return x, aux


def _lm_head(params, x):
    if "lm_head" in params:
        return x @ params["lm_head"]
    return x @ params["embed"].T


lm_head = _lm_head  # public: engine reads logits at packed-job positions


def _hybrid_forward(lp, cfg, h, positions, segment_ids):
    """Hymba: parallel attention + mamba heads, head-normed and averaged."""
    hd = cfg.resolved_head_dim
    b, s, _ = h.shape
    q, k, v = qkv_project(lp["attn"], h, cfg.num_heads, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    attn_out = attention(q, repeat_kv(k, cfg.q_per_kv),
                         repeat_kv(v, cfg.q_per_kv), causal=True,
                         window=cfg.sliding_window, segment_ids=segment_ids)
    attn_out = head_rms_norm(attn_out)
    ssm_out, ssm_state = ssm_lib.mamba_block(lp["mamba"], h,
                                             segment_ids=segment_ids)
    ssm_out = head_rms_norm(ssm_out.reshape(b, s, cfg.num_heads, hd))
    fused = 0.5 * (attn_out + ssm_out)
    out = fused.reshape(b, s, cfg.num_heads * hd) @ lp["w_fuse"]
    return out, ((k, v), ssm_state)


# ===========================================================================
# KV / state caches
# ===========================================================================


def init_cache(cfg: ModelConfig, batch_size: int, capacity: int,
               dtype=None) -> Cache:
    """Zero-initialised decode cache; shape contract for serve_step."""
    dtype = dtype or cfg.activation_dtype
    hd = cfg.resolved_head_dim
    cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
    layers = []
    kv_int8 = cfg.kv_cache_dtype == "int8"
    kv_dtype = jnp.int8 if kv_int8 else dtype
    for i in range(cfg.num_layers):
        kind = cfg.layer_kind(i)
        c: Cache = {}
        if kind in ("attn", "hybrid"):
            c["k"] = jnp.zeros((batch_size, cap, cfg.num_kv_heads, hd),
                               kv_dtype)
            c["v"] = jnp.zeros((batch_size, cap, cfg.num_kv_heads, hd),
                               kv_dtype)
            if kv_int8:
                c["k_scale"] = jnp.zeros(
                    (batch_size, cap, cfg.num_kv_heads), jnp.float32)
                c["v_scale"] = jnp.zeros(
                    (batch_size, cap, cfg.num_kv_heads), jnp.float32)
        if kind == "cross" or (cfg.is_encdec and kind == "attn"):
            n_mem = (cfg.num_image_tokens if cfg.family == "vlm"
                     else cfg.num_audio_frames)
            c["ck"] = jnp.zeros((batch_size, n_mem, cfg.num_kv_heads, hd),
                                dtype)
            c["cv"] = jnp.zeros((batch_size, n_mem, cfg.num_kv_heads, hd),
                                dtype)
        if kind == "hybrid":
            inner = cfg.num_heads * hd
            c["ssm"] = jnp.zeros((batch_size, inner, cfg.ssm_state),
                                 jnp.float32)
            c["conv"] = jnp.zeros((batch_size, 3, inner), jnp.float32)
        if kind == "mlstm":
            ihd = int(cfg.d_model * cfg.ssm_proj_factor) // cfg.num_heads
            c["state"] = jnp.zeros((batch_size, cfg.num_heads, ihd, ihd),
                                   jnp.float32)
        if kind == "slstm":
            shd = cfg.d_model // cfg.num_heads
            zeros = jnp.zeros((batch_size, cfg.num_heads, shd), jnp.float32)
            c.update(c=zeros, n=zeros, h=zeros,
                     m=jnp.full((batch_size, cfg.num_heads, shd), -10.0))
        layers.append(c)
    if cfg.scan_layers:
        layers = stack_layers(layers, cfg)
    return {"layers": layers, "pos": jnp.zeros((), jnp.int32),
            "slot_mask": jnp.zeros((batch_size, cap), bool)}


def _write_kv(lc: Cache, name: str, new, pos, cfg: ModelConfig) -> None:
    """Write K or V into the cache, quantizing when kv_cache_dtype=int8."""
    if cfg.kv_cache_dtype == "int8":
        scale = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / 127.0
        q = jnp.round(new.astype(jnp.float32)
                      / jnp.maximum(scale, 1e-8)[..., None])
        lc[name] = _cache_write(lc[name], q.astype(jnp.int8), pos)
        lc[name + "_scale"] = _cache_write(lc[name + "_scale"], scale, pos)
    else:
        lc[name] = _cache_write(lc[name], new, pos)


def _read_kv(lc: Cache, name: str, cfg: ModelConfig):
    if cfg.kv_cache_dtype == "int8":
        return (lc[name].astype(cfg.activation_dtype)
                * lc[name + "_scale"][..., None].astype(
                    cfg.activation_dtype))
    return lc[name]


def _cache_write(buf, new, pos):
    """Ring-buffer write of ``new`` (B, S, ...) at absolute position ``pos``."""
    cap = buf.shape[1]
    s = new.shape[1]
    if s == 1:
        return jax.lax.dynamic_update_slice_in_dim(
            buf, new.astype(buf.dtype), pos % cap, axis=1)
    slots = (jnp.arange(s) + pos) % cap
    if s >= cap:
        keep = slots[-cap:]
        return buf.at[:, keep].set(new[:, -cap:].astype(buf.dtype))
    return buf.at[:, slots].set(new.astype(buf.dtype))


# ===========================================================================
# prefill & decode
# ===========================================================================


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
            capacity: int, return_hidden: bool = False):
    """Run the full prompt, returning last-position logits and a primed
    cache positioned at ``seq_len``.

    ``batch["positions"]`` optionally overrides the RoPE positions (used by
    the engine's packed prefill, where several jobs share one row and each
    job carries the positions of its eventual decode-row layout).  With
    ``return_hidden`` (static under jit) the post-final-norm hidden states
    (B, S, d) are returned as a third output so callers can read logits at
    arbitrary positions — e.g. the last prompt token of every packed job —
    without materialising (B, S, V) logits."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache = init_cache(cfg, b, capacity)
    segment_ids = batch.get("segment_ids")
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    memory = None
    if cfg.is_encdec:
        memory = run_encoder(params, cfg, batch["enc_embeddings"])
    elif cfg.family == "vlm":
        memory = batch.get("image_embeddings")

    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scan_layers:
        p = cfg.scan_period()

        def unit(xc, unit_in):
            unit_params, unit_cache = unit_in
            new_caches = []
            for j in range(p):
                xc, lc = _prefill_layer(unit_params[j], unit_cache[j], xc,
                                        cfg, cfg.layer_kind(j), positions,
                                        segment_ids, memory)
                new_caches.append(lc)
            return xc, tuple(new_caches)

        x, new_layers = jax.lax.scan(
            unit, x, (tuple(params["layers"]), tuple(cache["layers"])))
        cache["layers"] = list(new_layers)
    else:
        for i, lp in enumerate(params["layers"]):
            x, lc = _prefill_layer(lp, cache["layers"][i], x, cfg,
                                   cfg.layer_kind(i), positions,
                                   segment_ids, memory)
            cache["layers"][i] = lc

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x[:, -1:])
    cache["pos"] = jnp.asarray(s, jnp.int32)
    if segment_ids is not None:
        # left-padded rows mark pad slots (segment < 0 convention) invalid
        cache["slot_mask"] = _cache_write(
            cache["slot_mask"], segment_ids >= 0, 0)
    else:
        cache["slot_mask"] = _cache_write(
            cache["slot_mask"], jnp.ones((b, s), bool), 0)
    if return_hidden:
        return logits, cache, x
    return logits, cache


def _prefill_layer(lp: Params, lc: Cache, x, cfg: ModelConfig, kind: str,
                   positions, segment_ids, memory):
    """One decoder block during prefill; returns (x, primed layer cache)."""
    lc = dict(lc)
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind == "attn":
        out, (k, v) = _self_attention(lp, cfg, h, positions, segment_ids)
        _write_kv(lc, "k", k, 0, cfg)
        _write_kv(lc, "v", v, 0, cfg)
        x = x + out
        if cfg.is_encdec:
            hx = rms_norm(x, lp["xnorm"], cfg.norm_eps)
            xo, (mk, mv) = _cross_attention(lp["xattn"], cfg, hx, memory)
            lc["ck"] = mk.astype(lc["ck"].dtype)
            lc["cv"] = mv.astype(lc["cv"].dtype)
            x = x + xo
    elif kind == "cross":
        out, (mk, mv) = _cross_attention(lp["attn"], cfg, h, memory)
        lc["ck"] = mk.astype(lc["ck"].dtype)
        lc["cv"] = mv.astype(lc["cv"].dtype)
        x = x + jnp.tanh(lp["xattn_gate"]).astype(x.dtype) * out
    elif kind == "hybrid":
        out, ((k, v), ssm_state) = _hybrid_forward(lp, cfg, h, positions,
                                                   segment_ids)
        _write_kv(lc, "k", k, 0, cfg)
        _write_kv(lc, "v", v, 0, cfg)
        lc["ssm"] = ssm_state["ssm"]
        lc["conv"] = ssm_state["conv"].astype(jnp.float32)
        x = x + out
    elif kind == "mlstm":
        out, state = ssm_lib.mlstm_block(lp["mlstm"], h,
                                         num_heads=cfg.num_heads,
                                         segment_ids=segment_ids)
        lc["state"] = state
        x = x + out
    elif kind == "slstm":
        out, state = ssm_lib.slstm_block(lp["slstm"], h,
                                         num_heads=cfg.num_heads,
                                         segment_ids=segment_ids)
        lc.update(state)
        x = x + out
    if cfg.d_ff:
        x = x + _ffn(lp, cfg, x)
    return x, lc


def _decode_layer(lp: Params, lc: Cache, x, cfg: ModelConfig, kind: str,
                  positions, pos, slot_mask, pallas_window=None):
    """One decoder block during decode; returns (x, updated layer cache).

    ``pallas_window`` is the layer-invariant (start, contiguous) analysis
    of ``slot_mask`` that decode_step computes once when the Pallas
    backend is active; None means use the jnp reference paths."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    lc = dict(lc)
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    if kind in ("attn", "hybrid"):
        q, k, v = qkv_project(lp["attn"], h, cfg.num_heads,
                              cfg.num_kv_heads, hd)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        _write_kv(lc, "k", k, pos, cfg)
        _write_kv(lc, "v", v, pos, cfg)
        kc = _read_kv(lc, "k", cfg)
        vc = _read_kv(lc, "v", cfg)
        if pallas_window is not None:
            # the kernel masks a per-row [start, valid_len) window, which
            # covers slot_mask exactly when each row has one contiguous
            # valid region (the engine's left-padded caches: pad prefix
            # invalid, slots [start, pos] written).  A mask with holes —
            # e.g. a future continuous-batching scheduler reusing freed
            # rows — falls back on device to the mask-honoring reference
            # path instead of silently attending to stale KV.
            start, contiguous = pallas_window
            attn_out = jax.lax.cond(
                contiguous,
                lambda args: pallas_kernels.gqa_decode(
                    args[0], args[1], args[2], pos + 1, start=start),
                lambda args: decode_attention_grouped(
                    args[0], args[1], args[2], pos + 1,
                    slot_mask=slot_mask),
                (q, kc, vc))
        elif cfg.grouped_decode:
            attn_out = decode_attention_grouped(
                q, kc, vc, pos + 1, window=cfg.sliding_window,
                slot_mask=slot_mask)
        else:
            attn_out = decode_attention(
                q, repeat_kv(kc, cfg.q_per_kv),
                repeat_kv(vc, cfg.q_per_kv), pos + 1,
                window=cfg.sliding_window, slot_mask=slot_mask)
        if kind == "attn":
            out = attn_out.reshape(b, 1, cfg.num_heads * hd) \
                @ lp["attn"]["wo"]
            x = x + out
            if cfg.is_encdec:
                hx = rms_norm(x, lp["xnorm"], cfg.norm_eps)
                xo = _cached_cross(lp["xattn"], cfg, hx, lc)
                x = x + xo
        else:  # hybrid
            ssm_out, new_state = ssm_lib.mamba_decode_step(
                lp["mamba"], h, {"ssm": lc["ssm"], "conv": lc["conv"]})
            lc["ssm"], lc["conv"] = new_state["ssm"], new_state["conv"]
            ssm_out = head_rms_norm(
                ssm_out.reshape(b, 1, cfg.num_heads, hd))
            fused = 0.5 * (head_rms_norm(attn_out) + ssm_out)
            x = x + fused.reshape(b, 1, cfg.num_heads * hd) @ lp["w_fuse"]
    elif kind == "cross":
        out = _cached_cross(lp["attn"], cfg, h, lc)
        x = x + jnp.tanh(lp["xattn_gate"]).astype(x.dtype) * out
    elif kind == "mlstm":
        out, state = ssm_lib.mlstm_decode_step(lp["mlstm"], h, lc["state"],
                                               num_heads=cfg.num_heads)
        lc["state"] = state
        x = x + out
    elif kind == "slstm":
        out, state = ssm_lib.slstm_decode_step(
            lp["slstm"], h, {k2: lc[k2] for k2 in ("c", "n", "h", "m")},
            num_heads=cfg.num_heads)
        lc.update(state)
        x = x + out
    if cfg.d_ff:
        x = x + _ffn(lp, cfg, x)
    return x, lc


def _decode_window(slot_mask, pos):
    """(start, contiguous) of a dense decode step's written slot mask.

    The Pallas decode kernel attends one [start, pos] window per row; it
    serves the step only when that covers ``slot_mask`` exactly (every
    row one contiguous valid region) — otherwise the step runs the
    mask-honouring reference attention (see ``_decode_layer``)."""
    start = jnp.argmax(slot_mask, axis=1).astype(jnp.int32)
    slots = jnp.arange(slot_mask.shape[1])[None, :]
    contiguous = jnp.all(
        slot_mask == ((slots >= start[:, None]) & (slots < pos + 1)))
    return start, contiguous


def decode_falls_back(cfg: ModelConfig, cache: Cache, new_cache: Cache):
    """int32 1 when the dense Pallas decode step ``cache`` -> ``new_cache``
    took the reference branch of its ``lax.cond``, else 0."""
    if not _pallas_attention_ok(cfg) or "slot_mask" not in cache:
        return jnp.zeros((), jnp.int32)
    _, contiguous = _decode_window(new_cache["slot_mask"], cache["pos"])
    return (~contiguous).astype(jnp.int32)


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Cache) -> Tuple[jnp.ndarray, Cache]:
    """One decode step.  token: (B, 1) int32 -> logits (B, 1, V).

    Dispatches on the cache structure: a paged cache (page pool + per-row
    page tables, see ``init_paged_cache``) routes to the paged decode
    path; the dense per-row cache keeps the original layout."""
    if "page_table" in cache:
        return _paged_decode_step(params, cfg, token, cache)
    b = token.shape[0]
    pos = cache["pos"]
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    x = jnp.take(params["embed"], token, axis=0)
    slot_mask = _cache_write(cache["slot_mask"],
                             jnp.ones((b, 1), bool), pos)

    pallas_window = None
    if _pallas_attention_ok(cfg):
        # layer-invariant: analyse the slot mask once per decode step
        pallas_window = _decode_window(slot_mask, pos)

    if cfg.scan_layers:
        p = cfg.scan_period()

        def unit(xc, unit_in):
            unit_params, unit_cache = unit_in
            new_caches = []
            for j in range(p):
                xc, lc = _decode_layer(unit_params[j], unit_cache[j], xc,
                                       cfg, cfg.layer_kind(j), positions,
                                       pos, slot_mask, pallas_window)
                new_caches.append(lc)
            return xc, tuple(new_caches)

        x, new_layers = jax.lax.scan(
            unit, x, (tuple(params["layers"]), tuple(cache["layers"])))
        new_layers = list(new_layers)
    else:
        new_layers = []
        for i, lp in enumerate(params["layers"]):
            x, lc = _decode_layer(lp, cache["layers"][i], x, cfg,
                                  cfg.layer_kind(i), positions, pos,
                                  slot_mask, pallas_window)
            new_layers.append(lc)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x)
    return logits, {"layers": new_layers, "pos": pos + 1,
                    "slot_mask": slot_mask}


def _cached_cross(attn_p, cfg, h, lc):
    hd = cfg.resolved_head_dim
    b = h.shape[0]
    q = (h @ attn_p["wq"]).reshape(b, 1, cfg.num_heads, hd)
    out = decode_attention(q, repeat_kv(lc["ck"], cfg.q_per_kv),
                           repeat_kv(lc["cv"], cfg.q_per_kv),
                           jnp.asarray(lc["ck"].shape[1], jnp.int32))
    return out.reshape(b, 1, cfg.num_heads * hd) @ attn_p["wo"]


# ===========================================================================
# paged KV cache (shared page pool + per-row page tables)
# ===========================================================================
#
# Host-side page accounting (allocator, radix prefix index, COW planning)
# lives in serving/paging.py; this section is the pure device math: a
# per-layer K/V pool of (num_pages, page_size, Hkv, hd), rows addressing
# it through (B, P) page tables, RoPE positions CANONICAL (token i of a
# row at position i) so one page's KV is bit-reusable by every row whose
# prompt shares that chunk.  Page 0 is the null page: dead/overflow rows
# write there and live attention never reads it unmasked.


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None):
    """Zero-initialised shared page pool: per layer, K and V tensors of
    (num_pages, page_size, Hkv, hd).  Covers pure-attention decoders with
    a float KV dtype only (``InferenceEngine.can_page`` gates)."""
    dtype = dtype or cfg.activation_dtype
    if cfg.kv_cache_dtype == "int8":
        raise ValueError("paged cache does not support int8 KV")
    if cfg.scan_layers or cfg.is_encdec:
        raise ValueError("paged cache requires a plain decoder")
    hd = cfg.resolved_head_dim
    layers = []
    for i in range(cfg.num_layers):
        if cfg.layer_kind(i) != "attn":
            raise ValueError("paged cache requires pure-attention layers")
        layers.append({
            "k": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, hd),
                           dtype),
            "v": jnp.zeros((num_pages, page_size, cfg.num_kv_heads, hd),
                           dtype),
        })
    return layers


def paged_prefill(params: Params, cfg: ModelConfig, tokens, positions,
                  page_table, dst_page, dst_slot, layers):
    """Prefill each row's NOVEL suffix into the shared page pool.

    tokens/positions/dst_page/dst_slot: (B, S) left-padded suffixes — pads
    carry position 0 and scatter into the null page.  page_table: (B, P)
    covering each row's prompt pages; prefix pages already hold committed
    (or COW-copied) KV.  Per layer the suffix K/V are scattered into the
    pool FIRST, then attention gathers prefix+suffix through the page
    table under one position-causal mask — so shared prefixes are read,
    never recomputed.  Returns (last-position logits (B, V), new layers).
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    new_layers = []
    for i, lp in enumerate(params["layers"]):
        x, lc = _paged_prefill_layer(lp, layers[i], x, cfg, positions,
                                     page_table, dst_page, dst_slot)
        new_layers.append(lc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _lm_head(params, x[:, -1]), new_layers


def _paged_prefill_layer(lp: Params, lc: Cache, x, cfg: ModelConfig,
                         positions, page_table, dst_page, dst_slot):
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    q, k, v = qkv_project(lp["attn"], h, cfg.num_heads, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kp = lc["k"].at[dst_page, dst_slot].set(k.astype(lc["k"].dtype))
    vp = lc["v"].at[dst_page, dst_slot].set(v.astype(lc["v"].dtype))
    if _pallas_attention_ok(cfg):
        attn_out = pallas_kernels.paged_prefill(q, kp, vp, page_table,
                                                positions)
    else:
        attn_out = kernels_ref.paged_prefill_ref(q, kp, vp, page_table,
                                                 positions)
    x = x + attn_out.reshape(b, s, cfg.num_heads * hd) @ lp["attn"]["wo"]
    if cfg.d_ff:
        x = x + _ffn(lp, cfg, x)
    return x, {"k": kp, "v": vp}


def _paged_decode_step(params: Params, cfg: ModelConfig, token, cache):
    """One decode step against a paged cache.

    cache: {"layers": [{"k","v"} per layer over the pool], "page_table":
    (B, P) int32, "row_len": (B,) int32}.  The next token of row b sits at
    canonical position row_len[b] and its K/V land at page
    page_table[b, row_len // page_size], slot row_len % page_size; the
    page column is clamped to the table width so overflow (and harvested
    rows, whose table is zeroed) write the null page harmlessly."""
    b = token.shape[0]
    pt = cache["page_table"]
    rl = cache["row_len"]
    ps = cache["layers"][0]["k"].shape[1]
    positions = rl[:, None]
    page_col = jnp.minimum(rl // ps, pt.shape[1] - 1)
    dst_page = pt[jnp.arange(b), page_col]
    dst_slot = rl % ps
    x = jnp.take(params["embed"], token, axis=0)
    new_layers = []
    for i, lp in enumerate(params["layers"]):
        x, lc = _paged_decode_layer(lp, cache["layers"][i], x, cfg,
                                    positions, pt, rl, dst_page, dst_slot)
        new_layers.append(lc)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _lm_head(params, x)
    return logits, {"layers": new_layers, "page_table": pt,
                    "row_len": rl + 1}


def _paged_decode_layer(lp: Params, lc: Cache, x, cfg: ModelConfig,
                        positions, page_table, row_len, dst_page, dst_slot):
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    h = rms_norm(x, lp["norm1"], cfg.norm_eps)
    q, k, v = qkv_project(lp["attn"], h, cfg.num_heads, cfg.num_kv_heads, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kp = lc["k"].at[dst_page, dst_slot].set(k[:, 0].astype(lc["k"].dtype))
    vp = lc["v"].at[dst_page, dst_slot].set(v[:, 0].astype(lc["v"].dtype))
    valid = row_len + 1
    if _pallas_attention_ok(cfg):
        attn_out = pallas_kernels.paged_gqa_decode(q, kp, vp, page_table,
                                                   valid)
    else:
        attn_out = kernels_ref.paged_gqa_decode_ref(q[:, 0], kp, vp,
                                                    page_table,
                                                    valid)[:, None]
    x = x + attn_out.reshape(b, 1, cfg.num_heads * hd) @ lp["attn"]["wo"]
    if cfg.d_ff:
        x = x + _ffn(lp, cfg, x)
    return x, {"k": kp, "v": vp}


# ===========================================================================
# losses
# ===========================================================================


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    mask = mask.astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
