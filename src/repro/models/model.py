"""LM assembly: embedding, scan-over-layers blocks, loss, prefill, decode.

Layer parameters are stacked with a leading ``(L, ...)`` axis and the depth
dimension is executed with ``lax.scan`` — HLO size is O(1) in depth (the
88-layer mistral-large-123b compiles in seconds) and the remat policy is
applied per layer.

Families:
  dense  : attn + MLP
  moe    : attn + MoE (paper-technique dispatch, see models/moe.py)
  ssm    : Mamba2 block only
  hybrid : parallel attn + SSM heads (Hymba), then MLP

A ``sharder(name, x)`` callback threads activation sharding constraints in
from the launch layer without making models mesh-aware.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, common, mlp as mlp_mod, moe as moe_mod, ssm as ssm_mod
from repro.models.attention import init_cache
from repro.models.config import ModelConfig
from repro.models.ssm import init_ssm_cache

Sharder = Callable[[str, jax.Array], jax.Array]


def _noop_sharder(name: str, x: jax.Array) -> jax.Array:
    return x


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key: jax.Array, cfg: ModelConfig) -> dict:
    dt = param_dtype(cfg)
    ks = common.split_keys(key, 4)
    p: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "hybrid"):
        p["attn_norm"] = jnp.ones((cfg.d_model,), dt)
        p["attn"] = attention.attn_init(ks[0], cfg, dt)
    if cfg.family in ("ssm", "hybrid"):
        p["ssm_norm"] = jnp.ones((cfg.d_model,), dt)
        p["ssm"] = ssm_mod.ssm_init(ks[1], cfg, dt)
    if cfg.family == "moe":
        p["mlp_norm"] = jnp.ones((cfg.d_model,), dt)
        p["moe"] = moe_mod.moe_init(ks[2], cfg, dt)
    elif cfg.family in ("dense", "hybrid"):
        p["mlp_norm"] = jnp.ones((cfg.d_model,), dt)
        p["mlp"] = mlp_mod.mlp_init(ks[3], cfg, dt)
    return p


def init_params(key: jax.Array, cfg: ModelConfig) -> dict:
    cfg.validate()
    dt = param_dtype(cfg)
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    params = {
        "embed": common.embed_init(k_embed, (cfg.vocab_padded, cfg.d_model), dt),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = common.dense_init(
            k_head, (cfg.d_model, cfg.vocab_padded), cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# Layer bodies (full sequence)
# ---------------------------------------------------------------------------


def _layer_fwd(lp: dict, x: jax.Array, positions: jax.Array,
               cfg: ModelConfig, sharder: Sharder) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        x = x + ssm_mod.ssm_layer(
            lp["ssm"], common.rms_norm(x, lp["ssm_norm"], cfg.norm_eps), cfg)
        return sharder("hidden", x), aux
    if cfg.family == "hybrid":
        h = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        a = attention.attention(lp["attn"], h, positions, cfg)
        hs = common.rms_norm(x, lp["ssm_norm"], cfg.norm_eps)
        s = ssm_mod.ssm_layer(lp["ssm"], hs, cfg)
        x = x + 0.5 * (a + s)            # parallel heads, mean-fused (Hymba)
    else:
        h = common.rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        x = x + attention.attention(lp["attn"], h, positions, cfg)
    x = sharder("hidden", x)
    h = common.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_layer(lp["moe"], h, cfg, sharder)
        x = x + y
    else:
        x = x + mlp_mod.mlp(lp["mlp"], h, cfg)
    return sharder("hidden", x), aux


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def forward_hidden(params: dict, cfg: ModelConfig, x: jax.Array,
                   positions: jax.Array,
                   sharder: Sharder = _noop_sharder) -> Tuple[jax.Array, jax.Array]:
    """Embedded input (B,S,D) -> final hidden (B,S,D), summed aux loss."""

    def body(carry, lp):
        h, aux = carry
        h, a = _layer_fwd(lp, h, positions, cfg, sharder)
        return (h, aux + a), None

    body = _remat_wrap(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               params["layers"])
    x = common.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def embed_inputs(params: dict, cfg: ModelConfig, batch: dict) -> jax.Array:
    """tokens (B,S) int or input_embeds (B,S,D) -> (B,S,D)."""
    if "input_embeds" in batch:
        x = batch["input_embeds"].astype(param_dtype(cfg))
    else:
        x = params["embed"][batch["tokens"]]
    if cfg.pos_emb == "sinusoidal":
        s = x.shape[1]
        pe = common.sinusoidal_pos_emb(jnp.arange(s), cfg.d_model)
        x = x + pe[None].astype(x.dtype)
    return x


def logits_fn(params: dict, cfg: ModelConfig, hidden: jax.Array,
              sharder: Sharder = _noop_sharder) -> jax.Array:
    """(B,S,D) -> (B,S,V_pad) fp32, pad vocab masked to -inf."""
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", hidden, head).astype(jnp.float32)
    pad = jnp.arange(cfg.vocab_padded) >= cfg.vocab_size
    logits = jnp.where(pad[None, None, :], -1e9, logits)
    return sharder("logits", logits)


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------


def train_loss(params: dict, cfg: ModelConfig, batch: dict,
               sharder: Sharder = _noop_sharder,
               aux_coeff: float = 0.01) -> Tuple[jax.Array, dict]:
    x = embed_inputs(params, cfg, batch)
    x = sharder("hidden", x)
    s = x.shape[1]
    positions = jnp.arange(s)
    hidden, aux = forward_hidden(params, cfg, x, positions, sharder)
    logits = logits_fn(params, cfg, hidden, sharder)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = nll.sum() / denom
    total = ce + aux_coeff * aux / max(cfg.n_layers, 1)
    return total, {"ce": ce, "aux": aux, "tokens": denom}


# ---------------------------------------------------------------------------
# Prefill / decode
# ---------------------------------------------------------------------------


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dt = param_dtype(cfg)
    cache: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "hybrid"):
        cache["kv"] = init_cache(cfg, batch, max_len, dt)
    if cfg.family in ("ssm", "hybrid"):
        cache["ssm"] = init_ssm_cache(cfg, batch, dt)
    return cache


def prefill(params: dict, cfg: ModelConfig, batch: dict, max_len: int,
            sharder: Sharder = _noop_sharder) -> Tuple[jax.Array, dict]:
    """Run the prompt, build the decode cache.

    Returns (last-position logits (B, V_pad), cache).
    """
    x = embed_inputs(params, cfg, batch)
    x = sharder("hidden", x)
    b, s = x.shape[:2]
    positions = jnp.arange(s)
    cache = init_decode_cache(cfg, b, max_len)
    dt = param_dtype(cfg)

    kv = cache.get("kv")
    sc = cache.get("ssm")

    def body(carry, lp):
        h, aux = carry
        new_rows = {}
        if cfg.family == "ssm":
            hn = common.rms_norm(h, lp["ssm_norm"], cfg.norm_eps)
            y, cx, cbc, hstate = ssm_mod.ssm_layer(lp["ssm"], hn, cfg,
                                                   return_cache=True)
            h = h + y
            new_rows["conv_x"], new_rows["conv_bc"] = cx, cbc
            new_rows["h"] = hstate
        else:
            hn = common.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            q, k, v = attention._project_qkv(lp["attn"], hn, positions, cfg)
            a = attention.flash_attention(q, k, v, positions, positions,
                                          cfg.sliding_window)
            a = attention._finish(lp["attn"], a, cfg)
            # keep the last S_cache tokens, at ring slots pos % S_cache so
            # decode's write cursor stays consistent
            s_cache = kv["k"].shape[2]
            keep = min(s_cache, s)
            slots = jnp.arange(s - keep, s, dtype=jnp.int32) % s_cache
            kshape = (b, s_cache, cfg.n_kv_eff, cfg.head_dim)
            if cfg.kv_cache_dtype == "int8":
                kq, ks = attention.quantize_kv(k[:, s - keep:])
                vq, vs = attention.quantize_kv(v[:, s - keep:])
                new_rows["k"] = jnp.zeros(kshape, jnp.int8).at[:, slots].set(kq)
                new_rows["v"] = jnp.zeros(kshape, jnp.int8).at[:, slots].set(vq)
                new_rows["k_scale"] = jnp.zeros(
                    kshape[:-1], jnp.bfloat16).at[:, slots].set(ks)
                new_rows["v_scale"] = jnp.zeros(
                    kshape[:-1], jnp.bfloat16).at[:, slots].set(vs)
            else:
                new_rows["k"] = jnp.zeros(kshape, dt).at[:, slots].set(
                    k[:, s - keep:])
                new_rows["v"] = jnp.zeros_like(new_rows["k"]).at[:, slots].set(
                    v[:, s - keep:])
            if cfg.family == "hybrid":
                hs = common.rms_norm(h, lp["ssm_norm"], cfg.norm_eps)
                ys, cx, cbc, hstate = ssm_mod.ssm_layer(lp["ssm"], hs, cfg,
                                                        return_cache=True)
                h = h + 0.5 * (a + ys)
                new_rows["conv_x"], new_rows["conv_bc"] = cx, cbc
                new_rows["h"] = hstate
            else:
                h = h + a
            hn2 = common.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if cfg.family == "moe":
                y, a2 = moe_mod.moe_layer(lp["moe"], hn2, cfg, sharder)
                h, aux = h + y, aux + a2
            else:
                h = h + mlp_mod.mlp(lp["mlp"], hn2, cfg)
        return (sharder("hidden", h), aux), new_rows

    (hidden, _), rows = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    hidden = common.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, hidden[:, -1:], sharder)[:, 0]

    if kv is not None:
        s_cache = kv["k"].shape[2]
        keep = min(s_cache, s)
        slots = jnp.arange(s - keep, s, dtype=jnp.int32) % s_cache
        pos = jnp.full((s_cache,), -1, jnp.int32).at[slots].set(
            jnp.arange(s - keep, s, dtype=jnp.int32))
        cache["kv"] = {k_: rows[k_] for k_ in rows
                       if k_ in ("k", "v", "k_scale", "v_scale")}
        cache["kv"].update(positions=jnp.broadcast_to(pos, (b, s_cache)),
                           index=jnp.full((b,), s, jnp.int32))
    if sc is not None:
        cache["ssm"] = {"conv_x": rows["conv_x"], "conv_bc": rows["conv_bc"],
                        "h": rows["h"]}
    return logits, cache


def decode_step(params: dict, cfg: ModelConfig, token_or_embed: jax.Array,
                cache: dict, sharder: Sharder = _noop_sharder
                ) -> Tuple[jax.Array, dict]:
    """One decode step.

    token_or_embed: (B, 1) int32 tokens or (B, 1, D) embeddings. Each
    row decodes at its own position (the cache's per-row ``index``).
    Returns (logits (B, V_pad) fp32, updated cache).
    """
    kv = cache.get("kv")
    sc = cache.get("ssm")
    if token_or_embed.ndim == 2:
        x = params["embed"][token_or_embed]
    else:
        x = token_or_embed.astype(param_dtype(cfg))
    b = x.shape[0]
    pos = (kv["index"] if kv is not None
           else jnp.zeros((b,), jnp.int32))          # (B,) current positions
    if cfg.pos_emb == "sinusoidal":
        pe = common.sinusoidal_pos_emb(pos[:, None], cfg.d_model)
        x = x + pe.astype(x.dtype)

    rows_b = jnp.arange(b)
    if kv is not None:
        s_cache = kv["k"].shape[2]
        slot = (pos % s_cache).astype(jnp.int32)
        new_positions = kv["positions"].at[rows_b, slot].set(
            pos.astype(jnp.int32))
    else:
        slot = new_positions = None

    def body(carry, lp_row):
        h = carry
        lp, row = lp_row
        new_row = {}
        if cfg.family == "ssm":
            hn = common.rms_norm(h, lp["ssm_norm"], cfg.norm_eps)
            y, cx, cbc, hst = ssm_mod.ssm_decode_step(
                lp["ssm"], hn, row["conv_x"], row["conv_bc"], row["h"], cfg)
            h = h + y
            new_row["conv_x"], new_row["conv_bc"] = cx, cbc
            new_row["h"] = hst
        else:
            hn = common.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            k1, v1 = attention.decode_kv(lp["attn"], hn, pos, cfg)
            if cfg.kv_cache_dtype == "int8":
                k1q, k1s = attention.quantize_kv(k1)
                v1q, v1s = attention.quantize_kv(v1)
                new_row["k"] = row["k"].at[rows_b, slot].set(k1q)
                new_row["v"] = row["v"].at[rows_b, slot].set(v1q)
                new_row["k_scale"] = row["k_scale"].at[rows_b, slot].set(k1s)
                new_row["v_scale"] = row["v_scale"].at[rows_b, slot].set(v1s)
                layer_k = attention.dequantize_kv(
                    new_row["k"], new_row["k_scale"], param_dtype(cfg))
                layer_v = attention.dequantize_kv(
                    new_row["v"], new_row["v_scale"], param_dtype(cfg))
            else:
                layer_k = row["k"].at[rows_b, slot].set(k1)
                layer_v = row["v"].at[rows_b, slot].set(v1)
                new_row["k"], new_row["v"] = layer_k, layer_v
            a = attention.decode_attention(lp["attn"], hn, layer_k, layer_v,
                                           new_positions, pos, cfg)
            if cfg.family == "hybrid":
                hs = common.rms_norm(h, lp["ssm_norm"], cfg.norm_eps)
                ys, cx, cbc, hst = ssm_mod.ssm_decode_step(
                    lp["ssm"], hs, row["conv_x"], row["conv_bc"], row["h"],
                    cfg)
                h = h + 0.5 * (a + ys)
                new_row["conv_x"], new_row["conv_bc"] = cx, cbc
                new_row["h"] = hst
            else:
                h = h + a
            hn2 = common.rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = moe_mod.moe_layer(lp["moe"], hn2, cfg, sharder)
                h = h + y
            else:
                h = h + mlp_mod.mlp(lp["mlp"], hn2, cfg)
        return h, new_row

    rows_in = {}
    if kv is not None:
        rows_in["k"], rows_in["v"] = kv["k"], kv["v"]
        if cfg.kv_cache_dtype == "int8":
            rows_in["k_scale"] = kv["k_scale"]
            rows_in["v_scale"] = kv["v_scale"]
    if sc is not None:
        rows_in["conv_x"], rows_in["conv_bc"] = sc["conv_x"], sc["conv_bc"]
        rows_in["h"] = sc["h"]

    hidden, rows = jax.lax.scan(body, x, (params["layers"], rows_in))
    hidden = common.rms_norm(hidden, params["final_norm"], cfg.norm_eps)
    logits = logits_fn(params, cfg, hidden, sharder)[:, 0]

    new_cache = dict(cache)
    if kv is not None:
        new_cache["kv"] = {k_: rows[k_] for k_ in rows
                           if k_ in ("k", "v", "k_scale", "v_scale")}
        new_cache["kv"].update(positions=new_positions, index=pos + 1)
    if sc is not None:
        new_cache["ssm"] = {"conv_x": rows["conv_x"],
                            "conv_bc": rows["conv_bc"], "h": rows["h"]}
    return logits, new_cache
