"""TPU-native execution-granularity engine — the paper's SW+ design as a
first-class distributed MoE dispatch (DESIGN.md §2).

``sw_plus_ep_layer`` is the expert-parallel sort–compact dispatch:

* tokens are data-sharded and *replicated across the model axis* (they
  already are, in the Megatron activation layout), experts are sharded
  over the model axis (EP);
* each model shard selects the assignments routed to *its* experts,
  sort-compacts them into BM-aligned groups (the dynamic-coalescing pass —
  small logical granularity, contiguous physical access), and runs the
  grouped matmul on exactly those rows;
* partial token outputs are combined with ONE psum over the model axis per
  layer — the MoE dispatch costs no all-to-all at all in this layout.

This is the TPU translation of "small warps + ideal coalescing beats large
warps + control-flow hardware": the LW+ path (models/moe.py
dispatch_lw_plus) synchronizes every token through global capacity buffers
whose SPMD partitioning replicates expert compute across the data axis
(~10x waste, EXPERIMENTS.md §Perf H-A1); the SW+ path computes only real
assignments (+ tile-alignment padding) and communicates only the combined
output.

The grouped matmul here is the jnp block-gather formulation (one weight
tile gathered per BM row-block — the XLA-compilable equivalent of
``kernels/moe_gmm``; on TPU the Pallas kernel slots in per-shard).
"""

from __future__ import annotations

from typing import Optional, Tuple

# jax-containment (warpsim-lint): repro.core modules bind jax through the
# compat choke point instead of importing it — new jax surface stays in
# one reviewed place.
from repro import compat
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig

jax, jnp, _jax_sharding = compat.jax_modules()
Mesh = _jax_sharding.Mesh
P = _jax_sharding.PartitionSpec

_MESH: Optional[Mesh] = None
_DP = None


def set_mesh(mesh: Optional[Mesh], dp=None) -> None:
    """Install the mesh (and data axes) used by sw_plus_ep layers."""
    global _MESH, _DP
    _MESH = mesh
    _DP = dp


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def sw_plus_ep_layer(params: dict, x: jax.Array, cfg: ModelConfig,
                     dp=None, block: int = 128) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel SW+ dispatch. x: (B, S, D) sharded P(dp, None, None).

    Returns (y (B, S, D) same sharding, aux loss scalar).
    """
    mesh = _MESH
    assert mesh is not None, "granularity.set_mesh(mesh) required for sw_plus_ep"
    if dp is None:
        dp = _DP
    tp = mesh.shape["model"]
    e_eff = cfg.moe_experts_eff
    e_loc = e_eff // tp
    k = cfg.moe_top_k
    b, s, d = x.shape
    t = b * s
    dp_size = 1
    if dp:
        for a in (dp if isinstance(dp, tuple) else (dp,)):
            dp_size *= mesh.shape[a]
    t_loc = t // dp_size
    # Per-shard row budget: this shard's expected share of assignments,
    # with the capacity-factor slack, BM-aligned (+1 spill block).
    c_shard = _round_up(
        int(t_loc * k / tp * cfg.moe_capacity_factor) + block, block)

    def local_fn(router, w1, w3, w2, x_loc):
        # x_loc: (T_loc, D) replicated over "model"; w*: (E_loc, D, F).
        m_idx = jax.lax.axis_index("model")
        gates, idx, aux = moe_mod.router_probs({"router": router[0]}, x_loc,
                                               cfg)
        owner = idx // e_loc                              # (T_loc, k)
        local_e = jnp.where(owner == m_idx, idx % e_loc, e_loc)  # sentinel
        flat_e = local_e.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)          # mine first
        sorted_e = flat_e[order]
        sizes = jnp.bincount(flat_e, length=e_loc + 1)
        starts = jnp.concatenate([jnp.zeros((1,), sizes.dtype),
                                  jnp.cumsum(sizes)[:-1]])
        padded = ((sizes + block - 1) // block) * block
        grp_start = jnp.concatenate([jnp.zeros((1,), padded.dtype),
                                     jnp.cumsum(padded)[:-1]])
        rank = (jnp.arange(flat_e.size, dtype=jnp.int32)
                - starts[sorted_e].astype(jnp.int32))
        dest = grp_start[sorted_e].astype(jnp.int32) + rank
        keep = (sorted_e < e_loc) & (dest < c_shard)      # mine & in budget

        token_src = (order // k).astype(jnp.int32)
        dest_c = jnp.where(keep, dest, c_shard - 1)
        src_c = jnp.where(keep, token_src, 0)
        # Dynamic coalescing: contiguous expert-sorted layout (C_shard, D).
        # (.add so dropped assignments' zero rows never clobber real rows)
        x_sorted = jnp.zeros((c_shard, d), x_loc.dtype)
        x_sorted = x_sorted.at[dest_c].add(
            jnp.where(keep[:, None], x_loc[src_c], 0))

        nblk = c_shard // block
        row_block = jnp.arange(nblk, dtype=jnp.int32) * block
        block_expert = jnp.searchsorted(
            jnp.cumsum(padded[:e_loc]), row_block, side="right"
        ).astype(jnp.int32)
        block_expert = jnp.minimum(block_expert, e_loc - 1)

        # Block-gather grouped matmul (jnp equivalent of kernels/moe_gmm).
        xb = x_sorted.reshape(nblk, block, d)
        h = jnp.einsum("gbd,gdf->gbf", xb, w1[block_expert])
        h = jax.nn.silu(h) * jnp.einsum("gbd,gdf->gbf", xb, w3[block_expert])
        out = jnp.einsum("gbf,gfd->gbd", h, w2[block_expert])
        out = out.reshape(c_shard, d)

        gate_flat = gates.reshape(-1).astype(x_loc.dtype)[order]
        contrib = out[dest_c] * jnp.where(keep, gate_flat, 0)[:, None]
        y = jnp.zeros((t_loc, d), x_loc.dtype).at[src_c].add(contrib)
        # Combine expert contributions across the model axis (each token's
        # k experts live on <= k shards): one psum per layer.
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return y, aux

    dp_spec = dp if dp else None
    fn = compat.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None, None),          # router (lead dim 1)
                  P("model", None, None),       # w1 (E, D, F) EP
                  P("model", None, None),
                  P("model", None, None),
                  P(dp_spec, None)),            # x (T, D)
        out_specs=(P(dp_spec, None), P()),
        check_vma=False,
    )
    y, aux = fn(params["router"][None], params["w1"], params["w3"],
                params["w2"], x.reshape(t, d))
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# H-C2: sequence-sharded flash decoding (unpadded KV heads)
# ---------------------------------------------------------------------------


def seq_sharded_decode_attention(q: jax.Array, cache_k: jax.Array,
                                 cache_v: jax.Array,
                                 cache_positions: jax.Array, pos: jax.Array,
                                 window: Optional[int] = None,
                                 mesh: Optional[Mesh] = None) -> jax.Array:
    """Flash-decoding attention with the KV cache sharded by *sequence*
    over the model axis (EXPERIMENTS.md §Perf H-C2).

    Instead of padding KV heads to the TP degree (musicgen: 24 -> 32,
    +33% cache bytes), the cache keeps its original heads and splits the
    sequence dim across model shards. Each shard computes partial
    online-softmax statistics (m, l, acc) over its slice; the combine is
    three tiny collectives (pmax + 2 psum of (B, H, hd)-sized tensors).

    q: (B, H, hd) one-token queries (real heads only);
    cache_k/v: (B, Sc, H, hd) — Sc sharded over "model";
    cache_positions: (Sc,) (-1 = empty). Returns (B, H, hd).
    """
    mesh = mesh or _MESH
    assert mesh is not None
    hd = q.shape[-1]
    scale = 1.0 / (hd ** 0.5)

    def local_fn(q_loc, k_loc, v_loc, pos_loc):
        s = jnp.einsum("bhd,bkhd->bhk", q_loc.astype(jnp.float32) * scale,
                       k_loc.astype(jnp.float32))
        valid = (pos_loc >= 0) & (pos_loc <= pos)
        if window is not None:
            valid &= (pos - pos_loc) < window
        s = jnp.where(valid[None, None, :], s, -2.0e38)
        m_i = s.max(-1)                                   # (B, H)
        p = jnp.exp(s - m_i[..., None])
        l_i = p.sum(-1)
        acc_i = jnp.einsum("bhk,bkhd->bhd", p, v_loc.astype(jnp.float32))
        m = jax.lax.pmax(m_i, "model")
        corr = jnp.exp(m_i - m)
        l = jax.lax.psum(l_i * corr, "model")
        acc = jax.lax.psum(acc_i * corr[..., None], "model")
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_loc.dtype)

    fn = compat.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, None, None),              # q replicated
                  P(None, "model", None, None),     # k: seq sharded
                  P(None, "model", None, None),
                  P("model",)),                     # positions
        out_specs=P(None, None, None),
        check_vma=False,
    )
    return fn(q, cache_k, cache_v, cache_positions)
