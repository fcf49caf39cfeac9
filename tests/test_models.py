"""Model stack: layer correctness, decode==forward, MoE dispatch
equivalence, SSD oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as kref
from repro.models import attention, common, model as M, moe as moe_mod, ssm as S
from repro.models.config import ModelConfig


def _cfg(family="dense", **kw):
    base = dict(name=f"t-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=128, dtype="float32")
    if family == "moe":
        base.update(d_ff=0, n_kv_heads=4,
                    moe_experts=8, moe_shared=1, moe_top_k=2, moe_d_ff=32)
    if family in ("ssm", "hybrid"):
        base.update(ssm_state=16, ssm_headdim=16, ssm_chunk=16)
    if family == "ssm":
        base.update(n_heads=1, n_kv_heads=1, pos_emb="none")
    base.update(kw)
    return ModelConfig(**base).validate()


# ----------------------------------------------------------------- layers

def test_rms_norm_unit_scale():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32)) * 10
    y = common.rms_norm(x, jnp.ones(32))
    rms = jnp.sqrt(jnp.mean(y * y, -1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, rtol=1e-3)


def test_rope_preserves_norm_and_relative_shift():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    pos = jnp.arange(8)
    y = common.apply_rope(x, pos, 10000.0)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)
    # dot(q_i, k_j) depends only on i-j
    q = jnp.ones((1, 8, 1, 16))
    k = jnp.ones((1, 8, 1, 16))
    qr = common.apply_rope(q, pos, 10000.0)[0, :, 0]
    kr = common.apply_rope(k, pos, 10000.0)[0, :, 0]
    d13 = float(qr[1] @ kr[3])
    d35 = float(qr[3] @ kr[5])
    assert d13 == pytest.approx(d35, rel=1e-5)


def test_flash_attention_vs_dense_reference():
    cfg = _cfg()
    b, s = 2, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (b, s, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(3), (b, s, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(4), (b, s, 2, 16))
    pos = jnp.arange(s)
    out = attention.flash_attention(q, k, v, pos, pos, None, kv_chunk=16)
    # dense reference with GQA expansion
    k2 = jnp.repeat(k, 2, axis=2)
    v2 = jnp.repeat(v, 2, axis=2)
    exp = kref.flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(-1, s, 16),
        k2.transpose(0, 2, 1, 3).reshape(-1, s, 16),
        v2.transpose(0, 2, 1, 3).reshape(-1, s, 16), causal=True)
    exp = exp.reshape(b, 4, s, 16).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_sliding_window_masks_old_tokens():
    b, s, w = 1, 32, 8
    q = jax.random.normal(jax.random.PRNGKey(5), (b, s, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(6), (b, s, 1, 16))
    v = jax.random.normal(jax.random.PRNGKey(7), (b, s, 1, 16))
    pos = jnp.arange(s)
    out_w = attention.flash_attention(q, k, v, pos, pos, w, kv_chunk=8)
    # manually windowed dense attention
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    mask = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < w)
    scores = jnp.where(mask[None, None], scores, -2e38)
    p = jax.nn.softmax(scores, -1)
    exp = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


def test_head_padding_zero_contribution():
    """TP pad heads must contribute nothing to the output."""
    cfg = _cfg(n_heads=3, n_kv_heads=3, tp_divisor=4)   # pads to 4
    assert cfg.n_q_eff == 4
    p = attention.attn_init(jax.random.PRNGKey(8), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 64))
    out = attention.attention(p, x, jnp.arange(8), cfg)
    # zero the pad-head weights: output must be identical (masked anyway)
    hd = cfg.head_dim
    p2 = dict(p)
    p2["wq"] = p["wq"].at[:, 3 * hd:].set(0)
    out2 = attention.attention(p2, x, jnp.arange(8), cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2),
                               rtol=1e-5, atol=1e-6)


# -------------------------------------------------------------------- MoE

@pytest.fixture(scope="module")
def moe_setup():
    cfg = _cfg("moe", moe_shared=0, moe_capacity_factor=8.0)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (96, 64))
    return cfg, params, x


def test_moe_lw_equals_oracle(moe_setup):
    cfg, params, x = moe_setup
    y_or, _ = moe_mod.dispatch_dense_oracle(params, x, cfg)
    y_lw, _ = moe_mod.dispatch_lw_plus(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_lw), np.asarray(y_or),
                               rtol=1e-4, atol=1e-5)


def test_moe_sw_equals_oracle(moe_setup):
    cfg, params, x = moe_setup
    y_or, _ = moe_mod.dispatch_dense_oracle(params, x, cfg)
    y_sw, _ = moe_mod.dispatch_sw_plus(params, x, cfg, block=64)
    np.testing.assert_allclose(np.asarray(y_sw), np.asarray(y_or),
                               rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens():
    """LW+ capacity sync: with tiny capacity, some tokens get zero output
    from the dropped assignment (paper: 'synchronizing through capacity')."""
    cfg = _cfg("moe", moe_shared=0, moe_capacity_factor=0.25)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    y_lw, _ = moe_mod.dispatch_lw_plus(params, x, cfg)
    y_or, _ = moe_mod.dispatch_dense_oracle(params, x, cfg)
    assert float(jnp.abs(y_lw - y_or).max()) > 1e-3


def test_moe_pad_experts_never_routed():
    cfg = _cfg("moe", moe_experts=6, tp_divisor=4)      # pads to 8
    assert cfg.moe_experts_eff == 8
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    _, idx, _ = moe_mod.router_probs(params, x, cfg)
    assert int(idx.max()) < 6


def test_sort_by_expert_layout():
    idx = jnp.asarray([[0, 2], [1, 2], [0, 1], [2, 0]])
    order, dest, block_expert, t_pad = moe_mod.sort_by_expert(idx, 4, block=4)
    flat = idx.reshape(-1)
    sorted_e = np.asarray(flat)[np.asarray(order)]
    assert (np.diff(sorted_e) >= 0).all()               # sorted by expert
    assert len(np.unique(np.asarray(dest))) == len(dest)  # injective
    be = np.asarray(block_expert)
    d = np.asarray(dest)
    for j, e in enumerate(sorted_e):                     # rows in own block
        assert be[d[j] // 4] == e


# -------------------------------------------------------------------- SSD

def test_ssd_chunked_vs_sequential():
    B, SQ, NH, P, N = 2, 48, 4, 8, 8
    x = jax.random.normal(jax.random.PRNGKey(0), (B, SQ, NH, P)) * 0.5
    dt = jax.random.normal(jax.random.PRNGKey(1), (B, SQ, NH))
    a_log = jnp.log(jnp.arange(1, NH + 1, dtype=jnp.float32))
    b = jax.random.normal(jax.random.PRNGKey(2), (B, SQ, 1, N)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(3), (B, SQ, 1, N)) * 0.3
    dsk = jnp.ones((NH,))
    y1, h1 = S.ssd_scan(x, dt, a_log, b, c, dsk, chunk=16)
    br = jnp.repeat(b, NH, 2)
    cr = jnp.repeat(c, NH, 2)
    y2, h2 = kref.ssd_chunk_ref(x, dt, a_log, br, cr, dsk, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               rtol=1e-4, atol=1e-4)


def test_ssd_chunk_size_invariance():
    B, SQ, NH, P, N = 1, 64, 2, 8, 4
    x = jax.random.normal(jax.random.PRNGKey(4), (B, SQ, NH, P)) * 0.5
    dt = jnp.zeros((B, SQ, NH))
    a_log = jnp.zeros((NH,))
    b = jax.random.normal(jax.random.PRNGKey(5), (B, SQ, 1, N)) * 0.3
    c = jax.random.normal(jax.random.PRNGKey(6), (B, SQ, 1, N)) * 0.3
    outs = [S.ssd_scan(x, dt, a_log, b, c, jnp.ones(NH), chunk=q)[0]
            for q in (8, 16, 32, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-4, atol=1e-4)


# -------------------------------------------- decode == full forward (all)

@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_decode_matches_forward(family):
    kw = {}
    if family == "hybrid":
        kw["sliding_window"] = 12
    if family == "moe":
        kw["moe_capacity_factor"] = 8.0
    cfg = _cfg(family, **kw)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, SQ = 2, 20
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, SQ), 0,
                              cfg.vocab_size)
    x = M.embed_inputs(params, cfg, {"tokens": toks})
    hid, _ = M.forward_hidden(params, cfg, x, jnp.arange(SQ))
    full = M.logits_fn(params, cfg, hid)
    lp, cache = M.prefill(params, cfg, {"tokens": toks[:, :6]}, max_len=SQ)
    errs = [float(jnp.abs(lp - full[:, 5]).max())]
    for t in range(6, SQ):
        lg, cache = M.decode_step(params, cfg, toks[:, t:t + 1], cache)
        errs.append(float(jnp.abs(lg - full[:, t]).max()))
    assert max(errs) < 2e-3, errs


def test_batched_server_ragged_slots_match_forward():
    """Continuous batching with ragged prompts: every request's logits at
    every step equal a full forward pass over its own tokens, whatever
    the other slots hold and wherever they are."""
    from repro.launch import serve as serve_lib

    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    reqs = [serve_lib.Request(i, rng.integers(0, cfg.vocab_size, size=n)
                              .astype(np.int32), 4, keep_logits=True)
            for i, n in enumerate((3, 9, 5))]
    serve_lib.BatchedServer(cfg, params, slots=2, max_len=32).run(reqs)
    for r in reqs:
        assert len(r.generated) == 4 and len(r.logits) == 4
        toks = np.concatenate([r.prompt, r.generated[:-1]])[None]
        x = M.embed_inputs(params, cfg, {"tokens": jnp.asarray(toks)})
        hid, _ = M.forward_hidden(params, cfg, x, jnp.arange(toks.shape[1]))
        full = np.asarray(M.logits_fn(params, cfg, hid))[0]
        want = full[len(r.prompt) - 1:]
        np.testing.assert_allclose(np.stack(r.logits), want, atol=2e-3)
        assert r.generated == [int(np.argmax(lg)) for lg in r.logits]


def test_train_loss_finite_and_masked():
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    labels = toks.at[:, :8].set(-1)       # mask half
    loss, metrics = M.train_loss(params, cfg, {"tokens": toks,
                                               "labels": labels})
    assert jnp.isfinite(loss)
    assert float(metrics["tokens"]) == 16.0
