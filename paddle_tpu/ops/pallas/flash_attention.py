"""Flash attention Pallas kernel for TPU.

Role of the reference's flash-attn CUDA integration
(phi fused attention kernels, UNVERIFIED). Layout: [B, S, H, D] in/out
(paddle convention); internally blocks over (batch*heads, q_blocks) with an
online-softmax accumulation loop over kv blocks — the classic TPU flash
forward. Backward is HAND-WRITTEN Pallas too (``_dkv_kernel`` /
``_dq_kernel`` below): bf16 operands with fp32 accumulation, recomputing
per-block logits from the saved log-sum-exp so memory stays O(S·D) (no
S×S materialization). Block sizes come from
``FLAGS_flash_attn_block_q/kv``; the best setting is config-dependent —
on v5e, 256/512 beats 512/512 by ~2 MFU points under remat at hidden
2560, while 512/512 won at the 0.89B sweet spot (see BASELINE.md for
the current tuning record).

GQA/MQA (fewer kv heads than q heads) is handled by repeating kv heads."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64



__all__ = ["flash_attention", "flash_attention_reference"]

_NEG_INF = -1e30

# sweep hook: the trial engine pins candidate blocks here (via
# force_blocks) while it compiles fresh variants — candidates must not
# ride set_flags, which would mark the flags user-explicit and defeat
# the override>cache>default precedence afterwards. THREAD-LOCAL: a
# tune-on-first-call search on one thread must not leak its trial
# blocks into unrelated traces on another.
import threading as _threading

_forced_tls = _threading.local()


class force_blocks:
    """Context manager pinning (block_q, block_kv) for trials (this
    thread only)."""

    def __init__(self, block_q, block_kv):
        self._val = (int(block_q), int(block_kv))

    def __enter__(self):
        self._prev = getattr(_forced_tls, "blocks", None)
        _forced_tls.blocks = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.blocks = self._prev
        return False


def _resolve_blocks(sq, sk, d, dtype):
    """(block_q, block_kv) for this shape, precedence (documented in
    framework/flags.py): forced trial candidate > explicit user flag
    (env or set_flags) > tuner cache > flag default. Host-side at
    trace time — blocks are static ints selecting the compiled grid."""
    from ...framework import flags
    forced = getattr(_forced_tls, "blocks", None)
    if forced is not None:
        return forced
    bq = int(flags.flag("FLAGS_flash_attn_block_q"))
    bkv = int(flags.flag("FLAGS_flash_attn_block_kv"))
    bq_explicit = flags.flag_source("FLAGS_flash_attn_block_q") != "default"
    bkv_explicit = flags.flag_source("FLAGS_flash_attn_block_kv") \
        != "default"
    if not (bq_explicit and bkv_explicit):
        from ...tuner import lookup
        cfg = lookup("flash_attention",
                     {"sq": int(sq), "sk": int(sk), "d": int(d)},
                     str(dtype))
        if cfg:
            if not bq_explicit:
                bq = int(cfg.get("block_q", bq))
            if not bkv_explicit:
                bkv = int(cfg.get("block_kv", bkv))
    return bq, bkv


def flash_attention_reference(q, k, v, causal=False, scale=None):
    """[B, S, H, D] reference (fp32 softmax)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len_q, seq_len_k):
    qi = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * scale  # [block_q, d]
    # bottom-right-aligned causal offset (standard flash/decode semantics):
    # query i may see keys k_pos <= i + (seq_len_k - seq_len_q)
    causal_offset = seq_len_k - seq_len_q

    def body(start_k, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.dslice(start_k * block_k, block_k),
                  slice(None)].astype(jnp.float32)
        v = v_ref[pl.dslice(start_k * block_k, block_k),
                  slice(None)].astype(jnp.float32)
        s = q @ k.T  # [block_q, block_k]
        k_pos = start_k * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len_k  # mask padded keys
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = valid & (q_pos + causal_offset >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_new, l_new

    d = q_ref.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    n_k_blocks = -(-seq_len_k // block_k)  # padded kv block count
    if causal:
        # only kv blocks up to this q block's last visible key
        # participate (weak python ints keep int32 here; the pallas_call
        # is traced under _no_x64)
        last_visible = (qi + 1) * block_q + causal_offset
        nk = (last_visible + (block_k - 1)) // block_k
        num_k = jnp.minimum(jnp.maximum(nk, 0), n_k_blocks)
    else:
        num_k = n_k_blocks
    acc, m, l = jax.lax.fori_loop(0, num_k, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    # stats ride a 128-lane last dim (TPU tiling requires the last block
    # dim be 128-divisible; same convention as jax's official kernel)
    lse_ref[:] = jnp.broadcast_to((m + jnp.log(l))[:, None],
                                  (block_q, 128))


def _round_up(n, m):
    return -(-n // m) * m


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, scale=None):
    out, _ = _flash_fwd(q, k, v, causal, scale)
    return out


def _flash_fwd(q, k, v, causal, scale):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if hk != h:  # GQA: repeat kv heads
        rep = h // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    bq, bkv = _resolve_blocks(sq, sk, d, q.dtype)
    block_q = min(bq, _round_up(sq, 8))
    block_k = min(bkv, _round_up(sk, 128))
    # [B, S, H, D] -> [B*H, S, D], padded to block multiples (the kernel
    # masks padded key positions; padded query rows are sliced off)
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    qh = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kh = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vh = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    if sq_p != sq:
        qh = jnp.pad(qh, ((0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        kh = jnp.pad(kh, ((0, 0), (0, sk_p - sk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, sk_p - sk), (0, 0)))
    grid = (b * h, sq_p // block_q)
    with _no_x64():
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=s, causal=causal,
                              block_q=block_q, block_k=block_k,
                              seq_len_q=sq, seq_len_k=sk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, block_q, d),
                             lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((None, sk_p, d), lambda bh, qi: (bh, 0, 0)),
                pl.BlockSpec((None, sk_p, d), lambda bh, qi: (bh, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d),
                             lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((None, block_q, 128),
                             lambda bh, qi: (bh, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
                jax.ShapeDtypeStruct((b * h, sq_p, 128), jnp.float32),
            ],
            name="flash_attention_fwd",
            interpret=_interpret(),
        )(qh, kh, vh)
    out4 = out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out4, lse[:, :sq, 0]


def _fwd_rule(q, k, v, causal, scale):
    out, lse = _flash_fwd(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                seq_len_q, seq_len_k):
    """One program owns one [block_k, d] kv block; loops over q blocks.
    Matmuls keep bf16 operands with fp32 accumulation (MXU-native)."""
    ki = pl.program_id(1)
    causal_offset = seq_len_k - seq_len_q
    k = k_ref[:]   # [block_k, d] input dtype
    v = v_ref[:]
    d = k_ref.shape[-1]

    def body(qi, carry):
        dk_acc, dv_acc = carry
        q = q_ref[pl.dslice(qi * block_q, block_q), slice(None)]
        g = g_ref[pl.dslice(qi * block_q, block_q), slice(None)]
        # lse/delta ride a lane-broadcast [sq_p, 128] layout (the fwd lse
        # convention — TPU tiling wants 128-lane tiles; reshaping across
        # lanes is an unsupported Mosaic shape cast, so read one column)
        lse = lse_ref[pl.dslice(qi * block_q, block_q), 0:1]
        delta = delta_ref[pl.dslice(qi * block_q, block_q), 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (q_pos < seq_len_q) & (k_pos < seq_len_k)
        if causal:
            valid = valid & (q_pos + causal_offset >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        # fully-masked rows have lse ~= -1e30, so exp(s - lse) would be 1
        # for masked entries — mask p explicitly, don't rely on s - lse
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        pb = p.astype(k.dtype)
        # dv += p^T @ g ; dp = g @ v^T ; ds = p*(dp-delta)*scale
        dv_acc = dv_acc + jax.lax.dot_general(
            pb, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    n_q_blocks = -(-seq_len_q // block_q)
    if causal:
        # first q block whose last row can see this kv block
        first = (ki * block_k - causal_offset) // block_q
        q_start = jnp.clip(first, 0, n_q_blocks)
    else:
        q_start = 0
    acc0 = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(q_start, n_q_blocks, body, acc0)
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref, *,
               scale, causal, block_q, block_k, seq_len_q, seq_len_k):
    """One program owns one [block_q, d] q block; loops over kv blocks."""
    qi = pl.program_id(1)
    causal_offset = seq_len_k - seq_len_q
    q = q_ref[:]
    g = g_ref[:]
    lse = lse_ref[:, 0:1]       # [block_q, 1] from the lane-broadcast tile
    delta = delta_ref[:, 0:1]
    d = q_ref.shape[-1]

    def body(ki, dq_acc):
        k = k_ref[pl.dslice(ki * block_k, block_k), slice(None)]
        v = v_ref[pl.dslice(ki * block_k, block_k), slice(None)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len_k
        if causal:
            valid = valid & (q_pos + causal_offset >= k_pos)
        s = jnp.where(valid, s, _NEG_INF)
        # explicit mask: see _dkv_kernel (fully-masked rows break s - lse)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_acc = dq_acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dq_acc

    n_k_blocks = -(-seq_len_k // block_k)
    if causal:
        last_visible = (qi + 1) * block_q + causal_offset
        nk = (last_visible + (block_k - 1)) // block_k
        num_k = jnp.minimum(jnp.maximum(nk, 0), n_k_blocks)
    else:
        num_k = n_k_blocks
    dq = jax.lax.fori_loop(0, num_k, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k_full, v_full, out, lse, g, causal, s):
    """Pallas backward: dkv kernel (grid over kv blocks) + dq kernel (grid
    over q blocks). All operands bf16 on the MXU, fp32 accumulators."""
    b, sq, h, d = q.shape
    sk = k_full.shape[1]
    bq, bkv = _resolve_blocks(sq, sk, d, q.dtype)
    # both block dims round up to 128 multiples: q blocks because the
    # lse/delta side inputs ride 128-lane tiles, kv blocks because the
    # dkv grid is sk_p/block_k programs and a non-divisor block would
    # leave trailing kv rows with no program (uninitialized dk/dv)
    block_q = min(_round_up(bq, 128), _round_up(sq, 128))
    block_k = min(_round_up(bkv, 128), _round_up(sk, 128))
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    bh = b * h

    def to_bh(x, s_len, s_pad):
        x = x.transpose(0, 2, 1, 3).reshape(bh, s_len, x.shape[-1])
        if s_pad != s_len:
            x = jnp.pad(x, ((0, 0), (0, s_pad - s_len), (0, 0)))
        return x

    qh = to_bh(q, sq, sq_p)
    kh = to_bh(k_full, sk, sk_p)
    vh = to_bh(v_full, sk, sk_p)
    gh = to_bh(g.astype(q.dtype), sq, sq_p)
    oh = to_bh(out, sq, sq_p)
    # delta = rowsum(g * out) in fp32; lse arrives as [bh, sq]
    delta = jnp.sum(gh.astype(jnp.float32) * oh.astype(jnp.float32), -1)
    lse_p = lse if lse.shape[1] == sq_p else jnp.pad(
        lse, ((0, 0), (0, sq_p - sq)))
    # lane-broadcast the per-row stats to 128-lane tiles (fwd lse
    # convention; Mosaic can't reshape across lanes)
    lse_p = jnp.broadcast_to(lse_p[..., None], (bh, sq_p, 128))
    delta = jnp.broadcast_to(delta[..., None], (bh, sq_p, 128))

    kw = dict(scale=s, causal=causal, block_q=block_q, block_k=block_k,
              seq_len_q=sq, seq_len_k=sk)
    with _no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **kw),
            grid=(bh, sk_p // block_k),
            in_specs=[
                pl.BlockSpec((None, sq_p, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, sq_p, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, sq_p, 128), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, sq_p, 128), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sk_p, d), k_full.dtype),
                jax.ShapeDtypeStruct((bh, sk_p, d), v_full.dtype),
            ],
            name="flash_attention_dkv",
            interpret=_interpret(),
        )(qh, kh, vh, gh, lse_p, delta)
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **kw),
            grid=(bh, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, sk_p, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block_q, 128),
                             lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block_q, 128),
                             lambda i, j: (i, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq_p, d), q.dtype),
            ],
            name="flash_attention_dq",
            interpret=_interpret(),
        )(qh, kh, vh, gh, lse_p, delta)[0]
    dq4 = dq[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk4 = dk[:, :sk].reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    dv4 = dv[:, :sk].reshape(b, h, sk, d).transpose(0, 2, 1, 3)
    return dq4, dk4, dv4


def _bwd_rule(causal, scale, res, g):
    q, k, v, out, lse = res
    from ...framework import flags
    if flags.flag("FLAGS_flash_attn_pallas_bwd"):
        b, sq, h, d = q.shape
        hk = k.shape[2]
        rep = h // hk
        k_full = jnp.repeat(k, rep, axis=2) if rep != 1 else k
        v_full = jnp.repeat(v, rep, axis=2) if rep != 1 else v
        s = scale if scale is not None else 1.0 / math.sqrt(d)
        dq4, dk4, dv4 = _flash_bwd_pallas(q, k_full, v_full, out,
                                          lse.reshape(b * h, sq), g,
                                          causal, s)
        if rep != 1:
            sk = k.shape[1]
            dk4 = dk4.reshape(b, sk, hk, rep, d).sum(3)
            dv4 = dv4.reshape(b, sk, hk, rep, d).sum(3)
        return (dq4.astype(q.dtype), dk4.astype(k.dtype),
                dv4.astype(v.dtype))
    return _bwd_rule_scan(causal, scale, res, g)


def _bwd_rule_scan(causal, scale, res, g):
    """Blockwise recompute backward (fp32 accumulation, O(S·D) memory)."""
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    rep = h // hk
    if rep != 1:
        k_full = jnp.repeat(k, rep, axis=2)
        v_full = jnp.repeat(v, rep, axis=2)
    else:
        k_full, v_full = k, v
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # [B,H,S,D] fp32
    qh = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kh = k_full.transpose(0, 2, 1, 3).astype(jnp.float32)
    vh = v_full.transpose(0, 2, 1, 3).astype(jnp.float32)
    gh = g.transpose(0, 2, 1, 3).astype(jnp.float32)
    oh = out.transpose(0, 2, 1, 3).astype(jnp.float32)
    lse_h = lse.reshape(b, h, sq)
    delta = jnp.sum(gh * oh, axis=-1)  # [B,H,Sq]

    # pad the key axis to the block multiple and mask padded keys —
    # never shrink the block (an odd sk would otherwise degrade to
    # block=1, i.e. a sequential per-position scan)
    block = 512
    sk_p = _round_up(sk, block)
    if sk_p != sk:
        kh = jnp.pad(kh, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    n_blocks = sk_p // block

    def kv_block(carry, i):
        dq_acc = carry
        ks = jax.lax.dynamic_slice_in_dim(kh, i * block, block, 2)
        vs = jax.lax.dynamic_slice_in_dim(vh, i * block, block, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, ks) * s
        k_pos = i * block + jax.lax.broadcasted_iota(
            jnp.int32, (sq, block), 1)
        valid = k_pos < sk  # padded keys contribute nothing
        if causal:
            q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, block), 0)
            # bottom-right aligned, matching the forward kernel
            valid = valid & (q_pos + (sk - sq) >= k_pos)
        logits = jnp.where(valid[None, None], logits, _NEG_INF)
        # explicit mask: fully-masked rows have lse ~= -1e30 and would
        # otherwise yield p = exp(0) = 1 on masked entries
        p = jnp.where(valid[None, None],
                      jnp.exp(logits - lse_h[..., None]), 0.0)
        dv_i = jnp.einsum("bhqk,bhqd->bhkd", p, gh)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gh, vs)
        ds = p * (dp - delta[..., None]) * s
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, ks)
        dk_i = jnp.einsum("bhqk,bhqd->bhkd", ds, qh)
        return dq_acc, (dk_i, dv_i)

    dq0 = jnp.zeros_like(qh)
    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        kv_block, dq0, jnp.arange(n_blocks))
    # [n_blocks, B, H, block, D] -> [B, H, Sk_p, D] -> slice true Sk
    dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(b, h, sk_p, d)[:, :, :sk]
    dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(b, h, sk_p, d)[:, :, :sk]
    if rep != 1:  # sum over repeated query-head groups
        dk = dk.reshape(b, hk, rep, sk, d).sum(2)
        dv = dv.reshape(b, hk, rep, sk, d).sum(2)
    dq4 = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    dk4 = dk.transpose(0, 2, 1, 3).astype(k.dtype)
    dv4 = dv.transpose(0, 2, 1, 3).astype(v.dtype)
    return dq4, dk4, dv4


flash_attention.defvjp(_fwd_rule, _bwd_rule)


# -- tunable surface ---------------------------------------------------------
# block_q/block_kv candidate grid, registered next to the knob. No
# cost_fn: flash byte traffic is block-invariant to first order (K/V
# blocks revisit across q programs — the BlockSpec index map is
# qi-independent), so the roofline cannot prove any candidate worse;
# every valid candidate gets timed. Shape key: (sq, sk, d).

def _register_flash_surface():
    from ...tuner.surface import TunableSurface, register_surface

    def _candidates(shape):
        return [{"block_q": bq, "block_kv": bkv}
                for bq in (128, 256, 512)
                for bkv in (128, 256, 512, 1024)]

    def _is_valid(config, shape):
        # fwd needs q blocks sublane-aligned, kv blocks lane-aligned;
        # the bwd kernels round both up to 128 so keep the grid there
        return (config["block_q"] % 128 == 0
                and config["block_kv"] % 128 == 0
                and config["block_q"] <= max(shape.get("sq", 1 << 30), 128)
                and config["block_kv"] <= max(shape.get("sk", 1 << 30),
                                              128))

    register_surface(TunableSurface(
        name="flash_attention",
        params=("block_q", "block_kv"),
        default={"block_q": 256, "block_kv": 512},
        candidates=_candidates,
        is_valid=_is_valid,
        describe="Flash-attention Pallas q/kv block sizes (fwd online-"
                 "softmax grid + hand-written bwd). Shape key: sq/sk/"
                 "head_dim. FLAGS_flash_attn_block_q/kv set explicitly "
                 "override any cached value."))


_register_flash_surface()


def flash_attention_cost(q_shape, kv_seq=None, causal=False, train=False):
    """Static FLOPs/bytes for one :func:`flash_attention` call (profiler
    cost-accounting surface): q [B, Sq, H, D]. Flash never materializes
    the [Sq, Sk] score matrix, so bytes count only q/k/v in + out —
    exactly why the kernel moves attention to the compute-bound side of
    the roofline. ``train=True`` multiplies by 3.5 (bwd recomputes the
    logits once on top of the 2x grad matmuls)."""
    from ...profiler.cost import attention_cost
    b, sq, h, d = (int(s) for s in q_shape)
    c = attention_cost(b, sq, h, d, kv_len=kv_seq, causal=causal)
    return c * 3.5 if train else c
