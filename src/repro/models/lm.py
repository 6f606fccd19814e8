"""Unified decoder LM over explicit parameter pytrees.

One model definition serves all ten assigned architectures: the token
mixer (GQA / MLA / hybrid attn+SSM / RWKV6) and the channel mixer
(dense gated MLP / top-k MoE) are selected by ``ModelConfig``.  Layers are
STACKED (leading L axis) and executed with lax.scan + remat, so the HLO is
depth-independent — crucial for CPU-hosted dry-run compiles of 40-64-layer
configs.

Sharding: parameters get explicit PartitionSpecs (``partition_specs``);
activations get in-graph constraints (``_constrain``) that no-op when no
mesh is active (single-device smoke tests).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..runtime import mesh as rmesh
from . import mla as mla_mod
from . import moe as moe_mod
from . import rwkv6 as rwkv_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import (
    gated_mlp,
    gqa_attention,
    gqa_decode,
    gqa_params_shape,
    mlp_params_shape,
    rms_norm,
)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# with_sharding_constraint / batch-axis resolution against the ambient
# mesh (see runtime.mesh).
_batch_axes = rmesh.batch_axes
_constrain = rmesh.constrain


def constrain_tokens(x):
    return _constrain(x, _batch_axes(), *([None] * (x.ndim - 1)))


# ------------------------------------------------------------- shapes ---

def mixer_params_shape(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.mixer == "gqa":
        return gqa_params_shape(cfg)
    if cfg.mixer == "mla":
        return mla_mod.mla_params_shape(cfg)
    if cfg.mixer == "hybrid":
        return ssm_mod.hybrid_params_shape(cfg)
    if cfg.mixer == "rwkv6":
        return rwkv_mod.rwkv6_params_shape(cfg)
    raise ValueError(cfg.mixer)


def mlp_params_shape_for(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.mlp == "dense":
        return mlp_params_shape(cfg)
    if cfg.mlp == "moe":
        return moe_mod.moe_params_shape(cfg)
    raise ValueError(cfg.mlp)


def layer_params_shape(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": (cfg.d_model,),
        "mixer": mixer_params_shape(cfg),
        "ln2": (cfg.d_model,),
        "mlp": mlp_params_shape_for(cfg),
    }


def padded_vocab(cfg: ModelConfig) -> int:
    """Pad the embedding row count so the vocab dim shards 16-way.

    Megatron-style: granite (49155), minicpm3 (73448), hymba (32001) are
    not divisible by the model-axis size; pad rows are ordinary learned
    rows that no label ever references (loss semantics unchanged up to the
    logsumexp over finite never-target logits).
    """
    v = cfg.vocab
    if v % 16 == 0:
        return v
    return ((v + 255) // 256) * 256


def param_shapes(cfg: ModelConfig):
    """Full-model ShapeDtypeStruct pytree (no allocation — dry-run input)."""
    dt = _dtype(cfg)
    L = cfg.n_layers
    V = padded_vocab(cfg)

    def stacked(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((L, *s), dt), tree,
            is_leaf=lambda s: isinstance(s, tuple),
        )

    out = {
        "embed": jax.ShapeDtypeStruct((V, cfg.d_model), dt),
        "layers": stacked(layer_params_shape(cfg)),
        "final_norm": jax.ShapeDtypeStruct((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = jax.ShapeDtypeStruct((cfg.d_model, V), dt)
    return out


def init_params(cfg: ModelConfig, key: jax.Array):
    """Real initialization (smoke tests / examples; small configs only)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(key, len(leaves))
    flat_paths = [
        "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(shapes)[0]
    ]

    def init_one(path, sds, k):
        shape, dt = sds.shape, sds.dtype
        name = path.split("/")[-1]
        if name.startswith("ln") or "norm" in name or name in (
                "ln_x", "attn_scale", "ssm_scale"):
            return jnp.ones(shape, dt)
        if name in ("dt_bias", "D", "u_bonus"):
            return jnp.ones(shape, dt) * 0.5
        if name == "A_log":
            return jnp.zeros(shape, dt)
        if name == "w_decay_base":
            return jnp.full(shape, -2.0, dt)
        if name == "mu":
            return jnp.full(shape, 0.5, dt)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    inits = [init_one(p, s, k) for p, s, k in zip(flat_paths, leaves, keys)]
    return jax.tree.unflatten(treedef, inits)


# --------------------------------------------------------- partitioning ---

_COL_PARALLEL = {"wq", "wk", "wv", "w1", "w3", "w_in", "w_B", "w_C",
                 "w_uq", "w_ukv", "w_dq", "w_dkv", "w_r", "w_k", "w_v",
                 "w_g", "w_decay_A"}
_ROW_PARALLEL = {"wo", "w2", "w_out", "w_o", "w_decay_B"}


def partition_specs(cfg: ModelConfig, mode: str = "fsdp"):
    """PartitionSpec pytree for params.

    mode "dp":   params replicated over data axes, TP over "model".
    mode "fsdp": additionally shard the non-TP major dim over data axes
                 (ZeRO-3 style; XLA inserts the all-gathers).
    MoE experts: TP over the ff dim (token-local math identical to dense
    TP); EP (experts over "model") is the hillclimb variant.
    """
    fsdp = ("pod", "data") if mode == "fsdp" else None

    def spec_for(path_name, shape, stacked):
        name = path_name
        lead = (None,) if stacked else ()
        nd = len(shape) - (1 if stacked else 0)
        if nd <= 1:
            return P(*lead, None) if nd == 1 else P(*lead)
        if name in ("w1", "w3", "w2") and nd == 3:      # MoE experts
            if name in ("w1", "w3"):
                return P(*lead, None, fsdp, "model")
            return P(*lead, None, "model", fsdp)
        if name in _COL_PARALLEL:
            return P(*lead, fsdp, "model")
        if name in _ROW_PARALLEL:
            return P(*lead, "model", fsdp)
        if name == "router":
            return P(*lead, None, None)
        if name == "conv":
            return P(*lead, None, None)
        if name == "embed":
            return P("model", fsdp)
        if name == "lm_head":
            return P(fsdp, "model")
        return P(*lead, *([None] * nd))

    shapes = param_shapes(cfg)

    def build(tree, stacked):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = build(v, stacked)
            else:
                out[k] = spec_for(k, v.shape, stacked)
        return out

    specs = {
        "embed": spec_for("embed", shapes["embed"].shape, False),
        "layers": build(shapes["layers"], True),
        "final_norm": P(None),
    }
    if "lm_head" in shapes:
        specs["lm_head"] = spec_for("lm_head", shapes["lm_head"].shape, False)
    return specs


def cache_specs(cfg: ModelConfig):
    """PartitionSpec pytree matching init_cache (stacked L leading dim).

    Batch shards over data axes.  The head-feature (last) dim shards over
    "model" rather than the kv-head dim: several assigned archs have fewer
    kv heads (starcoder2: 2, granite: 8) than the 16-way model axis, and
    dh=64..128 divides cleanly everywhere.  Slot writes
    (dynamic_update_slice over the sequence dim) stay shard-local.
    """
    dp = ("pod", "data")
    if cfg.mixer == "gqa":
        out = {
            "k": P(None, dp, None, None, "model"),
            "v": P(None, dp, None, None, "model"),
            "len": P(None),
        }
        if cfg.kv_cache_int8:
            # scales are dh-times smaller; keep them head-replicated so
            # the dequant multiply stays aligned with the dh-sharded values
            out["k_scale"] = P(None, dp, None, None)
            out["v_scale"] = P(None, dp, None, None)
        return out
    if cfg.mixer == "mla":
        # latent cache has no head dim; shard the latent dim over model
        return {"ckv": P(None, dp, None, "model"), "len": P(None)}
    if cfg.mixer == "hybrid":
        return {
            "attn": {
                "k": P(None, dp, None, None, "model"),
                "v": P(None, dp, None, None, "model"),
                "len": P(None),
            },
            "state": P(None, dp, None, None, "model"),
            "conv_tail": P(None, dp, None, "model"),
        }
    if cfg.mixer == "rwkv6":
        return {
            "state": P(None, dp, None, None, "model"),
            "x_tail": P(None, dp, None, "model"),
        }
    raise ValueError(cfg.mixer)


# -------------------------------------------------------------- forward ---

def _mixer_apply(p, x, cfg, positions=None):
    if cfg.mixer == "gqa":
        return gqa_attention(p, x, cfg, positions)[0]
    if cfg.mixer == "mla":
        return mla_mod.mla_attention(p, x, cfg, positions)[0]
    if cfg.mixer == "hybrid":
        return ssm_mod.hybrid_block(p, x, cfg, positions)[0]
    if cfg.mixer == "rwkv6":
        return rwkv_mod.rwkv6_mix(p, x, cfg)[0]
    raise ValueError(cfg.mixer)


def _mlp_apply(p, x, cfg):
    if cfg.mlp == "dense":
        return gated_mlp(p, x, cfg)
    return moe_mod.moe_block(p, x, cfg)


def _block(layer_p, h, cfg):
    ba = _batch_axes()
    h = _constrain(h, ba, None, None)
    h = h + _mixer_apply(layer_p["mixer"], rms_norm(h, layer_p["ln1"]), cfg)
    h = h + _mlp_apply(layer_p["mlp"], rms_norm(h, layer_p["ln2"]), cfg)
    return _constrain(h, ba, None, None)


def forward_hidden(params, cfg: ModelConfig, tokens=None, embeddings=None,
                   remat: bool = True,
                   remat_policy: Optional[str] = None):
    """Backbone only: tokens/embeddings -> final-norm hidden (B, S, d)."""
    if embeddings is not None:
        h = embeddings.astype(_dtype(cfg))
    else:
        h = jnp.take(params["embed"], tokens, axis=0)
    h = constrain_tokens(h)

    def body(h, layer_p):
        return _block(layer_p, h, cfg), None

    if remat:
        policy = None
        if remat_policy == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots
        elif remat_policy == "dots_no_batch":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        body = jax.checkpoint(body, policy=policy)
    h, _ = jax.lax.scan(body, h, params["layers"])
    return rms_norm(h, params["final_norm"])


def apply_head(params, h):
    """hidden (..., d) -> logits (..., V)."""
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = h @ head
    spec = [_batch_axes()] + [None] * (logits.ndim - 2) + ["model"]
    return _constrain(logits, *spec)


def forward(params, cfg: ModelConfig, tokens=None, embeddings=None,
            remat: bool = True, remat_policy: Optional[str] = None,
            last_only: bool = False):
    """tokens (B, S) int32 OR embeddings (B, S, d) -> logits (B, S, V).

    ``last_only`` computes the head projection only for the final position
    (serving prefill semantics) — on a 152k-vocab model that removes
    S-1/S of the head FLOPs and ALL the logits-sized collective traffic
    (hillclimb 2, EXPERIMENTS.md §Perf).
    """
    h = forward_hidden(params, cfg, tokens=tokens, embeddings=embeddings,
                       remat=remat, remat_policy=remat_policy)
    if last_only:
        h = h[:, -1:, :]
    return apply_head(params, h)


# --------------------------------------------------------------- decode ---

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               as_shapes: bool = False):
    """Stacked (L-leading) per-layer decode cache pytree."""
    dt = _dtype(cfg)
    L = cfg.n_layers

    def make(shape, dtype=dt):
        sds = jax.ShapeDtypeStruct((L, *shape), dtype)
        return sds if as_shapes else jnp.zeros(sds.shape, sds.dtype)

    def scalar_len():
        sds = jax.ShapeDtypeStruct((L,), jnp.int32)
        return sds if as_shapes else jnp.zeros(sds.shape, sds.dtype)

    if cfg.mixer == "gqa":
        C = min(max_len, cfg.window) if cfg.window > 0 else max_len
        if cfg.kv_cache_int8:
            return {
                "k": make((batch, C, cfg.n_kv_heads, cfg.d_head), jnp.int8),
                "v": make((batch, C, cfg.n_kv_heads, cfg.d_head), jnp.int8),
                "k_scale": make((batch, C, cfg.n_kv_heads), jnp.float32),
                "v_scale": make((batch, C, cfg.n_kv_heads), jnp.float32),
                "len": scalar_len(),
            }
        return {
            "k": make((batch, C, cfg.n_kv_heads, cfg.d_head)),
            "v": make((batch, C, cfg.n_kv_heads, cfg.d_head)),
            "len": scalar_len(),
        }
    if cfg.mixer == "mla":
        return {
            "ckv": make((batch, max_len, cfg.kv_lora_rank + cfg.rope_head_dim)),
            "len": scalar_len(),
        }
    if cfg.mixer == "hybrid":
        C = min(max_len, cfg.window) if cfg.window > 0 else max_len
        di = cfg.ssm_heads * cfg.d_head
        return {
            "attn": {
                "k": make((batch, C, cfg.n_kv_heads, cfg.d_head)),
                "v": make((batch, C, cfg.n_kv_heads, cfg.d_head)),
                "len": scalar_len(),
            },
            "state": make((batch, cfg.ssm_heads, cfg.ssm_state, cfg.d_head),
                          jnp.float32),
            "conv_tail": make((batch, ssm_mod.CONV_K - 1, di)),
        }
    if cfg.mixer == "rwkv6":
        return {
            "state": make((batch, cfg.ssm_heads, cfg.d_head, cfg.d_head),
                          jnp.float32),
            "x_tail": make((batch, 1, cfg.d_model)),
        }
    raise ValueError(cfg.mixer)


def _mixer_decode(p, x, cfg, cache):
    if cfg.mixer == "gqa":
        return gqa_decode(p, x, cfg, cache)
    if cfg.mixer == "mla":
        return mla_mod.mla_decode(p, x, cfg, cache)
    if cfg.mixer == "hybrid":
        return ssm_mod.hybrid_decode(p, x, cfg, cache)
    if cfg.mixer == "rwkv6":
        return rwkv_mod.rwkv6_decode(p, x, cfg, cache)
    raise ValueError(cfg.mixer)


def decode_step(params, cfg: ModelConfig, tokens, cache):
    """tokens (B, 1) + stacked cache -> (logits (B, V), new cache)."""
    h = jnp.take(params["embed"], tokens, axis=0)
    h = constrain_tokens(h)

    def body(h, xs):
        layer_p, layer_cache = xs
        hn = rms_norm(h, layer_p["ln1"])
        mix_out, new_cache = _mixer_decode(layer_p["mixer"], hn, cfg,
                                           layer_cache)
        h = h + mix_out
        h = h + _mlp_apply(layer_p["mlp"], rms_norm(h, layer_p["ln2"]), cfg)
        return h, new_cache

    h, new_caches = jax.lax.scan(body, h, (params["layers"], cache))
    h = rms_norm(h, params["final_norm"])
    head = params.get("lm_head")
    if head is None:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = h @ head
    return logits[:, 0, :], new_caches
