"""Core layers: norms, RoPE, GQA attention (chunked prefill + decode).

Everything is pure jnp over explicit parameter pytrees (no flax): params
are dicts of arrays, layer fns are (params, x, ...) -> y, so the whole
model scans over stacked per-layer params and lowers to a single compact
HLO loop regardless of depth.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Ambient-mesh-aware sharding annotations live in the runtime layer so
# they work on every supported JAX version (0.4.x lacks the explicit-
# sharding APIs these used to call directly).
from ..runtime.mesh import batch_axes, constrain


def rms_norm(x, gain, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * scale).astype(dt) * gain


def act_fn(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    raise ValueError(kind)


# ---------------------------------------------------------------- RoPE ---

def rope_angles(positions, dim: int, theta: float):
    """positions (...,) -> cos/sin (..., dim/2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (S, D/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., :, None, :]
    s = sin[..., :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


# ----------------------------------------------------- chunked attention ---

def chunked_causal_attention(q, k, v, chunk: int = 512,
                             window: int = 0, scale: Optional[float] = None):
    """Flash-style online-softmax attention, O(S * chunk) memory.

    q (B, S, H, D); k/v (B, S, Hkv, D) — GQA handled by head repetition at
    the logical level (XLA CSEs the broadcast).  ``window`` > 0 restricts
    attention to a trailing window (sliding-window attention); blocks
    entirely outside every query's window are masked (their contribution
    vanishes through the online-softmax weights).
    """
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    Hkv = k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # Pin the HEAD dim to the model axis (hillclimb 2): without this,
    # head counts that don't divide the 16-way axis (qwen3: 40 q / 8 kv)
    # push GSPMD into contraction-dim sharding, which all-reduces the f32
    # score tensor on EVERY kv chunk (~1.7 TB/device for prefill_32k).
    # GSPMD pads the head dim instead (<=20% extra head compute).
    ba = batch_axes()
    q = constrain(q, ba, None, "model", None)
    k = constrain(k, ba, None, "model", None)
    v = constrain(v, ba, None, "model", None)
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    nkv = max(1, S // chunk)
    ck = S // nkv
    kc = k.reshape(B, nkv, ck, H, D)
    vc = v.reshape(B, nkv, ck, H, Dv)
    q_pos = jnp.arange(S)
    qf = (q * scale).astype(jnp.float32)

    def step(carry, blk):
        m_run, l_run, acc = carry
        kb, vb, j = blk
        kv_pos = j * ck + jnp.arange(ck)
        scores = jnp.einsum("bshd,bchd->bhsc", qf, kb.astype(jnp.float32))
        mask = q_pos[None, None, :, None] >= kv_pos[None, None, None, :]
        if window > 0:
            mask &= (q_pos[None, None, :, None] - kv_pos[None, None, None, :]
                     < window)
        scores = jnp.where(mask, scores, -1e30)
        m_new = jnp.maximum(m_run, scores.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        # NOTE (hillclimb 2, refuted): casting p@v to bf16 was tried and
        # REGRESSED both accuracy and HLO traffic (extra converts) — keep
        # the f32 chain; see EXPERIMENTS.md §Perf.
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhsc,bchd->bhsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, H, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.arange(nkv)),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhsd->bshd", out).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, window: int = 0):
    """Single-token attention against a (possibly rolling) KV cache.

    q (B, 1, H, D); caches (B, C, Hkv, D); cache_len scalar = #valid slots.
    """
    B, _, H, D = q.shape
    C, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    if rep > 1:
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scores = jnp.einsum(
        "bshd,bchd->bhsc", (q / np.sqrt(D)).astype(jnp.float32),
        k_cache.astype(jnp.float32))
    pos = jnp.arange(C)
    valid = pos[None, None, None, :] < cache_len
    if window > 0:
        valid &= pos[None, None, None, :] >= jnp.maximum(cache_len - window, 0)
    scores = jnp.where(valid, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhsc,bchd->bshd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------------------ GQA block ---

def gqa_params_shape(cfg):
    d, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    shapes = {
        "wq": (d, H * dh),
        "wk": (d, Hkv * dh),
        "wv": (d, Hkv * dh),
        "wo": (H * dh, d),
    }
    if cfg.qk_norm:
        shapes["q_norm"] = (dh,)
        shapes["k_norm"] = (dh,)
    return shapes


def gqa_attention(p, x, cfg, positions=None):
    """Full-sequence (training / prefill) GQA attention."""
    B, S, d = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(B, S, H, dh)
    k = (x @ p["wk"]).reshape(B, S, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, S, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope:
        pos = positions if positions is not None else jnp.arange(S)
        cos, sin = rope_angles(pos, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    o = chunked_causal_attention(q, k, v, chunk=cfg.attn_chunk,
                                 window=cfg.window)
    return o.reshape(B, S, H * dh) @ p["wo"], (k, v)


def _quantize_kv(t):
    """(B,1,Hkv,dh) -> (int8 values, per (B,1,Hkv) scale)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def gqa_decode(p, x, cfg, cache):
    """One-token decode; cache = {"k": (B,C,Hkv,dh), "v": ..., "len": ()}.

    With ``cfg.kv_cache_int8`` the cache holds int8 values + per-token
    per-head scales (symmetric); dequantization happens at read.
    """
    B, S, d = x.shape
    assert S == 1
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(B, 1, H, dh)
    k = (x @ p["wk"]).reshape(B, 1, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, 1, Hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    pos = cache["len"]
    if cfg.rope:
        cos, sin = rope_angles(pos[None], dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    C = cache["k"].shape[1]
    # Rolling buffer when the cache is window-sized: every live slot is
    # inside the window by construction (RoPE phases are absolute, so dot
    # products stay relative-position-correct across wraparound).
    rolling = cfg.window > 0 and C <= cfg.window
    slot = pos % C if rolling else jnp.minimum(pos, C - 1)
    if cfg.kv_cache_int8:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice(cache["k"], kq,
                                               (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(cache["v"], vq,
                                               (0, slot, 0, 0))
        k_sc = jax.lax.dynamic_update_slice(
            cache["k_scale"], ks.astype(cache["k_scale"].dtype),
            (0, slot, 0))
        v_sc = jax.lax.dynamic_update_slice(
            cache["v_scale"], vs.astype(cache["v_scale"].dtype),
            (0, slot, 0))
        dt = jnp.dtype(cfg.dtype)
        k_full = (k_cache.astype(dt) * k_sc[..., None].astype(dt))
        v_full = (v_cache.astype(dt) * v_sc[..., None].astype(dt))
        o = decode_attention(q, k_full, v_full, pos + 1,
                             window=0 if rolling else cfg.window)
        new_cache = {"k": k_cache, "v": v_cache, "k_scale": k_sc,
                     "v_scale": v_sc, "len": pos + 1}
    else:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        o = decode_attention(q, k_cache, v_cache, pos + 1,
                             window=0 if rolling else cfg.window)
        new_cache = {"k": k_cache, "v": v_cache, "len": pos + 1}
    return o.reshape(B, 1, H * dh) @ p["wo"], new_cache


# ------------------------------------------------------------- gated MLP ---

def mlp_params_shape(cfg):
    d, ff = cfg.d_model, cfg.d_ff
    return {"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}


def gated_mlp(p, x, cfg):
    h = act_fn(x @ p["w1"], cfg.act) * (x @ p["w3"])
    return h @ p["w2"]
