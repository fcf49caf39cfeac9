"""GQA attention: chunked-flash training/prefill path and KV-cache decode.

Tile granularity (the paper's warp-size analogue on TPU) is explicit: the
training/prefill path processes KV in ``kv_chunk``-sized blocks with an
online-softmax scan — the block size is swept by the kernel benchmarks and
mirrors the Pallas kernel's BlockSpec tiling (``repro.kernels.flash_attention``).

Head-count padding for tensor parallelism follows ModelConfig: pad query
heads are zero-masked before the output projection.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.config import ModelConfig

NEG_INF = -2.0e38


def attn_init(key: jax.Array, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_q_eff, cfg.n_kv_eff
    ks = common.split_keys(key, 4)
    p = {
        "wq": common.dense_init(ks[0], (d, nq * hd), d, dtype),
        "wk": common.dense_init(ks[1], (d, nkv * hd), d, dtype),
        "wv": common.dense_init(ks[2], (d, nkv * hd), d, dtype),
        "wo": common.dense_init(ks[3], (nq * hd, d), nq * hd, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _head_mask(cfg: ModelConfig, dtype) -> jax.Array:
    """(nq_eff,) 1.0 for real heads, 0.0 for TP pad heads."""
    return (jnp.arange(cfg.n_q_eff) < cfg.n_heads).astype(dtype)


def _project_qkv(params: dict, x: jax.Array, positions: jax.Array,
                 cfg: ModelConfig):
    """x: (B, S, D) -> q (B,S,nq,hd), k/v (B,S,nkv,hd), roped + normed."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,df->bsf", x, params["wq"]).reshape(b, s, cfg.n_q_eff, hd)
    k = jnp.einsum("bsd,df->bsf", x, params["wk"]).reshape(b, s, cfg.n_kv_eff, hd)
    v = jnp.einsum("bsd,df->bsf", x, params["wv"]).reshape(b, s, cfg.n_kv_eff, hd)
    if cfg.qk_norm:
        q = common.head_rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = common.head_rms_norm(k, params["k_norm"], cfg.norm_eps)
    if cfg.pos_emb == "rope":
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _finish(params: dict, out: jax.Array, cfg: ModelConfig) -> jax.Array:
    """out: (B, S, nq, hd) -> (B, S, D), masking TP pad heads."""
    b, s = out.shape[:2]
    out = out * _head_mask(cfg, out.dtype)[None, None, :, None]
    out = out.reshape(b, s, cfg.n_q_eff * cfg.head_dim)
    return jnp.einsum("bsf,fd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Full-sequence attention (training / prefill)
# ---------------------------------------------------------------------------


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array, k_positions: jax.Array,
                    window: Optional[int], kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention over KV chunks.

    q: (B, Sq, nq, hd); k, v: (B, Sk, nkv, hd). Causal w.r.t. positions,
    optionally sliding-window. Returns (B, Sq, nq, hd).
    """
    b, sq, nq, hd = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    scale = 1.0 / (hd ** 0.5)
    qh = (q.reshape(b, sq, nkv, g, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32) * scale)                 # (B,nkv,G,Sq,hd)

    kv_chunk = min(kv_chunk, sk)
    if sk % kv_chunk:
        pad = kv_chunk - sk % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
        sk += pad
    nchunk = sk // kv_chunk
    kc = (k.reshape(b, nchunk, kv_chunk, nkv, hd)
          .transpose(1, 0, 3, 2, 4))                    # (N,B,nkv,C,hd)
    vc = (v.reshape(b, nchunk, kv_chunk, nkv, hd)
          .transpose(1, 0, 3, 2, 4))
    kpos = k_positions.reshape(nchunk, kv_chunk)

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, kp = blk
        s = jnp.einsum("bngqd,bnkd->bngqk", qh, kb.astype(jnp.float32))
        valid = q_positions[:, None] >= kp[None, :]      # (Sq, C) causal
        if window is not None:
            valid &= (q_positions[:, None] - kp[None, :]) < window
        valid &= (kp >= 0)[None, :]
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bngqk,bnkd->bngqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, nkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, nkv, g, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kpos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nq, hd)
    return out.astype(q.dtype)


def attention(params: dict, x: jax.Array, positions: jax.Array,
              cfg: ModelConfig, kv_chunk: int = 1024) -> jax.Array:
    """Full causal self-attention block (training / prefill). x: (B,S,D)."""
    q, k, v = _project_qkv(params, x, positions, cfg)
    out = flash_attention(q, k, v, positions, positions,
                          cfg.sliding_window, kv_chunk)
    return _finish(params, out, cfg)


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    """Per-layer stacked KV cache (plain dict so sharding/checkpoint rules
    can key on field names).

    k, v: (L, B, S_cache, nkv, hd); positions: (B, S_cache) (-1 = empty);
    index: (B,) next write cursor per row (monotone token position
    count), so rows of one batch may sit at different positions.
    For sliding-window configs S_cache == window and writes wrap (ring
    buffer); otherwise S_cache == max sequence length.
    """
    s_cache = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (cfg.n_layers, batch, s_cache, cfg.n_kv_eff, cfg.head_dim)
    cache = {
        "positions": jnp.full((batch, s_cache), -1, jnp.int32),
        "index": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.kv_cache_dtype == "int8":
        # Quantized KV: int8 payload + per-(token, head) bf16 scales
        # (+1.6% bytes). Halves the decode memory-roofline term vs bf16
        # (EXPERIMENTS.md §Perf H-C1).
        cache["k"] = jnp.zeros(shape, jnp.int8)
        cache["v"] = jnp.zeros(shape, jnp.int8)
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.bfloat16)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.bfloat16)
    else:
        cache["k"] = jnp.zeros(shape, dtype)
        cache["v"] = jnp.zeros(shape, dtype)
    return cache


def quantize_kv(x: jax.Array):
    """x: (..., hd) -> (int8 payload, bf16 scale over trailing dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def decode_attention(params: dict, x: jax.Array, layer_k: jax.Array,
                     layer_v: jax.Array, cache_positions: jax.Array,
                     pos: jax.Array, cfg: ModelConfig):
    """One-token attention against the cache for a single layer.

    x: (B, 1, D); layer_k/v: (B, S_cache, nkv, hd) *already updated* with
    this step's k/v; cache_positions: (B, S_cache); pos: (B,) each row's
    position. Returns (B, 1, D).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    nq, nkv = cfg.n_q_eff, cfg.n_kv_eff
    g = nq // nkv
    q, _, _ = _project_qkv(params, x, pos[:, None].astype(jnp.int32), cfg)
    qh = (q.reshape(b, 1, nkv, g, hd).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32)) / (hd ** 0.5)           # (B,nkv,G,1,hd)
    s = jnp.einsum("bngqd,bknd->bngqk", qh,
                   layer_k.astype(jnp.float32))          # (B,nkv,G,1,Sc)
    valid = (cache_positions >= 0) & (cache_positions <= pos[:, None])
    if cfg.sliding_window is not None:
        valid &= (pos[:, None] - cache_positions) < cfg.sliding_window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngqk,bknd->bngqd", p, layer_v.astype(jnp.float32))
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, 1, nq, hd).astype(x.dtype)
    return _finish(params, out, cfg)


def decode_kv(params: dict, x: jax.Array, pos: jax.Array, cfg: ModelConfig):
    """Project this step's k, v for cache insertion. x: (B,1,D); pos: (B,)."""
    _, k, v = _project_qkv(params, x, pos[:, None].astype(jnp.int32), cfg)
    return k[:, 0], v[:, 0]        # (B, nkv, hd)
