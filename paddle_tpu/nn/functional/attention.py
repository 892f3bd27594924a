"""Attention functionals.

``scaled_dot_product_attention`` mirrors paddle's API
(python/paddle/nn/functional/flash_attention.py, UNVERIFIED) and routes to
the Pallas flash-attention kernel on TPU (SURVEY.md §2.1: fused_attention /
flash-attn integration → Pallas), with a jnp reference path everywhere else.
Layout convention is paddle's: [batch, seq, num_heads, head_dim]."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply
from ...framework import flags
from ...ops.common import as_tensor

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "sdpa_reference", "sdpa_with_cache"]


def _use_pallas() -> bool:
    return (flags.flag("FLAGS_enable_pallas_kernels")
            and jax.default_backend() == "tpu")


def sdpa_reference(q, k, v, attn_mask=None, dropout_p=0.0, is_causal=False,
                   scale=None, dropout_key=None):
    """Pure-jnp reference attention on [B, S, H, D] arrays."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # GQA/MQA: repeat kv heads up to the query head count
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    # [B, H, S, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits,
                               jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    probs = probs.astype(v.dtype)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1 - dropout_p, probs.shape)
        probs = probs * keep / (1 - dropout_p)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Inputs [batch, seq, num_heads, head_dim] (paddle convention)."""
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    from ...amp.auto_cast import maybe_cast_matmul
    q, k = maybe_cast_matmul(q, k)
    _, v = maybe_cast_matmul(q, v)
    args = [q, k, v]
    if attn_mask is not None:
        args.append(as_tensor(attn_mask))

    use_pallas = (_use_pallas() and attn_mask is None and dropout_p == 0.0
                  and q.shape[1] == k.shape[1])
    if use_pallas:
        from ...ops.pallas._mesh import kernel_placement, sharded_heads
        use_pallas, mesh = kernel_placement()
    if use_pallas:
        from jax import ad_checkpoint

        from ...ops.pallas import flash_attention as fa

        def fn(qq, kk, vv):
            # per shard under a fleet mesh (ops/pallas/_mesh.py)
            out = sharded_heads(
                lambda a, b, c: fa.flash_attention(a, b, c,
                                                   causal=is_causal),
                mesh, qq, kk, vv)
            # name the kernel output so the opt-in remat policy
            # FLAGS_recompute_policy='dots_and_flash_saveable' can save
            # it (under dots_saveable a checkpointed layer re-runs the
            # flash forward in backward — it is not a dot)
            return ad_checkpoint.checkpoint_name(out, "flash_out")
        return apply(fn, q, k, v, name="flash_attention")

    key_rng = None
    if dropout_p > 0.0 and training:
        from ...framework import random as fr
        key_rng = fr.default_generator.next_key()

    def fn(qq, kk, vv, *m):
        return sdpa_reference(qq, kk, vv, m[0] if m else None,
                              dropout_p if key_rng is not None else 0.0,
                              is_causal, dropout_key=key_rng)
    return apply(fn, *args, name="sdpa")


def sdpa_with_cache(query, key, value, k_cache, v_cache, pos):
    """Incremental-decoding attention over a static-shape KV cache.

    Writes ``key``/``value`` (new tokens, [B, S, KV, D]) into the caches
    ([B, max_len, KV, D]) at sequence offset ``pos`` (int32 scalar tensor,
    traceable), then attends ``query`` over the whole cache with the
    positional causal mask ``cache_index <= pos + query_index``. Covers both
    prefill (S = prompt len, pos = 0) and decode (S = 1, pos = current len)
    uniformly. Role of the reference's decoder ``cache_kv`` path in
    fused_multi_head_attention / PaddleNLP decoding (mount empty, no cites).

    Returns ``(out, new_k_cache, new_v_cache)``.
    """
    q = as_tensor(query)
    k, v = as_tensor(key), as_tensor(value)
    kc, vc = as_tensor(k_cache), as_tensor(v_cache)
    p = as_tensor(pos)

    def fn(qq, kk, vv, kcache, vcache, pp):
        pp = pp.astype(jnp.int32)
        start = (jnp.zeros((), jnp.int32), pp,
                 jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        kcache = jax.lax.dynamic_update_slice(
            kcache, kk.astype(kcache.dtype), start)
        vcache = jax.lax.dynamic_update_slice(
            vcache, vv.astype(vcache.dtype), start)
        s, max_len = qq.shape[1], kcache.shape[1]
        mask = (jnp.arange(max_len)[None, :]
                <= pp + jnp.arange(s)[:, None])          # [S, max_len]
        out = sdpa_reference(qq, kcache.astype(qq.dtype),
                             vcache.astype(qq.dtype),
                             attn_mask=mask[None, None])
        return out, kcache, vcache

    return apply(fn, q, k, v, kc, vc, p, n_outputs=3, name="sdpa_cached")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """Block-sparse attention with a CSR sparsity pattern
    (paddle.nn.functional.sparse_attention parity). q/k/v:
    [B, H, S, D]; offset [B, H, S+1], columns [B, H, nnz] — row i of the
    attention matrix only attends to the listed columns.

    TPU formulation: a dense masked softmax built FROM the CSR pattern
    (scatter of the column lists into a [S, S] mask) — on TPU the MXU
    prefers the dense masked matmul over gather-based sparsity at these
    block sizes; the CSR arguments keep the reference's contract."""
    import jax
    import jax.numpy as jnp

    from ...framework.core import apply
    from ...ops.common import as_tensor

    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    off, cols = as_tensor(sparse_csr_offset), as_tensor(sparse_csr_columns)

    def fn(qq, kk, vv, offsets, columns, *rest):
        import math as _math
        b, h, s, d = qq.shape
        nnz = columns.shape[-1]

        def one_mask(off1, col1):
            # row id of every nnz entry from the CSR offsets
            counts = off1[1:] - off1[:-1]               # [S]
            rows = jnp.repeat(jnp.arange(s), counts.astype(jnp.int32),
                              total_repeat_length=nnz)
            m = jnp.zeros((s, s), jnp.bool_)
            return m.at[rows, col1.astype(jnp.int32)].set(True)

        mask = jax.vmap(jax.vmap(one_mask))(offsets, columns)  # [B,H,S,S]
        logits = jnp.einsum("bhqd,bhkd->bhqk", qq, kk,
                            preferred_element_type=jnp.float32)
        logits = logits / _math.sqrt(d)
        if rest:
            logits = logits + rest[0].astype(logits.dtype)
        logits = jnp.where(mask, logits, -1e30)
        p = jax.nn.softmax(logits, -1).astype(vv.dtype)
        # rows with an empty pattern must output zeros, not uniform noise
        any_row = mask.any(-1, keepdims=True)
        p = p * any_row.astype(p.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    args = [q, k, v, off, cols]
    if attn_mask is not None:
        args.append(as_tensor(attn_mask))
    return apply(fn, *args, name="sparse_attention")


__all__ += ["sparse_attention"]
