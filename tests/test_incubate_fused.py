"""Round-3 incubate fused-op long tail vs naive numpy/jnp oracles."""

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.incubate.nn import functional as IF


def test_fused_bias_dropout_residual_layer_norm_eval():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 8).astype(np.float32)
    res = rng.randn(2, 5, 8).astype(np.float32)
    b = rng.randn(8).astype(np.float32)
    out = IF.fused_bias_dropout_residual_layer_norm(
        paddle.to_tensor(x), paddle.to_tensor(res), paddle.to_tensor(b),
        dropout_rate=0.3, training=False)
    h = res + (x + b)
    mu = h.mean(-1, keepdims=True)
    var = h.var(-1, keepdims=True)
    ref = (h - mu) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=1e-4,
                               atol=1e-5)


def test_masked_multihead_attention_matches_dense():
    rng = np.random.RandomState(1)
    B, H, T, D = 2, 3, 6, 4
    lens = np.array([3, 5], np.int32)     # tokens already cached
    packed = rng.randn(B, 3 * H * D).astype(np.float32)
    cache = np.zeros((2, B, H, T, D), np.float32)
    for bi in range(B):
        cache[:, bi, :, :lens[bi]] = rng.randn(2, H, lens[bi],
                                               D).astype(np.float32)
    out, new_cache = IF.masked_multihead_attention(
        paddle.to_tensor(packed), paddle.to_tensor(cache),
        sequence_lengths=paddle.to_tensor(lens), num_heads=H, head_dim=D)
    out_np = np.asarray(out.numpy())
    nc = np.asarray(new_cache.numpy())
    q = packed.reshape(B, 3, H, D)[:, 0]
    k_new = packed.reshape(B, 3, H, D)[:, 1]
    v_new = packed.reshape(B, 3, H, D)[:, 2]
    for bi in range(B):
        L = lens[bi] + 1
        kc = np.concatenate([cache[0, bi, :, :lens[bi]],
                             k_new[bi][:, None]], axis=1)
        vc = np.concatenate([cache[1, bi, :, :lens[bi]],
                             v_new[bi][:, None]], axis=1)
        lg = np.einsum("hd,htd->ht", q[bi], kc) / np.sqrt(D)
        p = np.exp(lg - lg.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        ref = np.einsum("ht,htd->hd", p, vc).reshape(H * D)
        np.testing.assert_allclose(out_np[bi], ref, rtol=1e-4, atol=1e-5)
        # cache got the new k at position lens
        np.testing.assert_allclose(nc[0, bi, :, lens[bi]], k_new[bi],
                                   rtol=1e-6)


def test_variable_length_attention_matches_full_on_unpadded():
    rng = np.random.RandomState(2)
    B, H, S, D = 2, 2, 5, 4
    q = rng.randn(B, H, S, D).astype(np.float32)
    k = rng.randn(B, H, S, D).astype(np.float32)
    v = rng.randn(B, H, S, D).astype(np.float32)
    full = np.array([S, S], np.int32)
    out = IF.variable_length_memory_efficient_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(full), paddle.to_tensor(full))
    lg = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(np.asarray(out.numpy()), ref, rtol=1e-4,
                               atol=1e-5)
    # ragged: padded kv rows must not contribute
    lens = np.array([3, 5], np.int32)
    out2 = np.asarray(IF.variable_length_memory_efficient_attention(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(lens), paddle.to_tensor(lens)).numpy())
    kc, vc = k[0, :, :3], v[0, :, :3]
    lg0 = np.einsum("hqd,hkd->hqk", q[0, :, :3], kc) / np.sqrt(D)
    p0 = np.exp(lg0 - lg0.max(-1, keepdims=True))
    p0 = p0 / p0.sum(-1, keepdims=True)
    ref0 = np.einsum("hqk,hkd->hqd", p0, vc)
    np.testing.assert_allclose(out2[0, :, :3], ref0, rtol=1e-4,
                               atol=1e-5)
    assert np.allclose(out2[0, :, 3:], 0.0)   # padded query rows zeroed


def test_fused_moe_matches_loop():
    rng = np.random.RandomState(3)
    N, d, E, f, K = 6, 8, 4, 16, 2
    x = rng.randn(N, d).astype(np.float32)
    g = rng.randn(d, E).astype(np.float32)
    up = rng.randn(E, d, f).astype(np.float32)
    down = rng.randn(E, f, d).astype(np.float32)
    out = np.asarray(IF.fused_moe(
        paddle.to_tensor(x), paddle.to_tensor(g), paddle.to_tensor(up),
        paddle.to_tensor(down), top_k=K).numpy())

    def gelu(a):
        from scipy.special import erf
        return 0.5 * a * (1 + erf(a / np.sqrt(2)))

    logits = x @ g
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    ref = np.zeros_like(x)
    for n in range(N):
        idx = np.argsort(-probs[n])[:K]
        w = probs[n, idx] / probs[n, idx].sum()
        for j, e in enumerate(idx):
            ref[n] += w[j] * (gelu(x[n] @ up[e]) @ down[e])
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_fused_ec_moe_runs():
    rng = np.random.RandomState(4)
    N, d, E, f = 5, 6, 3, 12
    out = IF.fused_ec_moe(
        paddle.to_tensor(rng.randn(1, N, d).astype(np.float32)),
        paddle.to_tensor(rng.randn(d, E).astype(np.float32)),
        paddle.to_tensor(rng.randn(E, d, f).astype(np.float32)),
        paddle.to_tensor(rng.randn(E, f).astype(np.float32)),
        paddle.to_tensor(rng.randn(E, f, d).astype(np.float32)),
        paddle.to_tensor(rng.randn(E, d).astype(np.float32)))
    assert list(out.shape) == [1, N, d]
    assert np.isfinite(np.asarray(out.numpy())).all()


def test_block_multihead_attention_aliases_paged():
    from paddle_tpu.ops.paged_attention import paged_attention_reference
    import jax.numpy as jnp
    rng = np.random.RandomState(5)
    B, H, D, P, page = 2, 2, 4, 5, 4
    q = rng.randn(B, H, D).astype(np.float32)
    kp = rng.randn(P, page, H * D).astype(np.float32)
    vp = rng.randn(P, page, H * D).astype(np.float32)
    tables = np.array([[1, 2], [3, 4]], np.int32)
    lens = np.array([5, 7], np.int32)
    out = np.asarray(IF.block_multihead_attention(
        paddle.to_tensor(q), paddle.to_tensor(kp), paddle.to_tensor(vp),
        paddle.to_tensor(tables), paddle.to_tensor(lens)).numpy())
    ref = np.asarray(paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lens)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)
