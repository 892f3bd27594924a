"""Fused RMSNorm Pallas kernel (role of phi fused rms_norm, UNVERIFIED).

Forward is a row-wise reduction + scale — one VMEM pass per block of rows.
Backward uses a custom VJP with a fused Pallas kernel for dx and an XLA
reduction for dw (dw is a full-rows reduction; XLA's tree reduction over
HBM is already optimal for it).

:func:`rms_norm_residual` is the Liger-style residual-add variant for the
decoder hot path: one VMEM pass reads ``x`` and ``res`` and writes BOTH
``y = rmsnorm(x + res) * w`` and ``r = x + res`` — the residual stream
never makes a separate HBM round trip through an add op. The backward
kernel fuses dx/dres (they are the same tensor: d(x+res) distributes)
with the rmsnorm dx math, so the pair costs one extra output, not an
extra pass."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64



__all__ = ["rms_norm", "rms_norm_reference", "rms_norm_residual",
           "rms_norm_residual_reference", "rms_norm_cost"]


def rms_norm_reference(x, w, eps=1e-6):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    normed = x * jax.lax.rsqrt(ms + eps)
    o_ref[:] = (normed * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _dx_kernel(x_ref, w_ref, g_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    d = x.shape[-1]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    gw = g * w
    # dx = inv * gw - x * inv^3 * mean(gw * x)
    dot = jnp.mean(gw * x, axis=-1, keepdims=True)
    o_ref[:] = (inv * gw - x * (inv ** 3) * dot).astype(o_ref.dtype)


# sweep hook (same contract as flash_attention.force_blocks): trials
# pin a candidate here instead of going through the tuner cache.
# Thread-local so one thread's trial never leaks into another's trace.
import threading as _threading

_forced_tls = _threading.local()


class force_rows_block:
    """Context manager pinning the rows-per-program block for trials
    (this thread only)."""

    def __init__(self, block_rows):
        self._val = int(block_rows)

    def __enter__(self):
        self._prev = getattr(_forced_tls, "rows_block", None)
        _forced_tls.rows_block = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.rows_block = self._prev
        return False


#: elements of one (rows, d) stream block. The residual backward keeps
#: five such streams double-buffered next to its f32 temporaries, and
#: the TPU compiler gives a kernel 16 MB of scoped VMEM: 256 rows fit
#: up to d = 2560, and overflow at d = 4096 (16.0 MB fwd / 16.8 MB bwd,
#: compiled for a described v5e). Half a million elements per stream
#: keeps every width inside it.
_BLOCK_ELEMS = 512 * 1024


def _max_rows(d) -> int:
    """Largest power-of-two row block the scoped-VMEM budget allows at
    feature dim ``d`` (128 rows at 3584/4096, 256 at 2048, never < 8)."""
    cap = max(8, _BLOCK_ELEMS // max(int(d), 1))
    return 1 << (cap.bit_length() - 1)


def _rows_valid(config, shape) -> bool:
    b = config["block_rows"]
    return b > 0 and b % 8 == 0 and b <= _max_rows(shape.get("d", 1))


def _clamp_rows(want, n_rows, d, forced) -> int:
    if d is not None and not forced:
        want = min(want, _max_rows(d))   # a trial's block is its own
    return min(want, -(-n_rows // 8) * 8)


def _rows_block(n_rows: int, d: int | None = None, dtype=None) -> int:
    """Rows per program, clamped to the (8-aligned) row count and to
    what scoped VMEM holds at this width. 256 is the static pick; the
    tuner cache ("rms_norm" surface, keyed by feature dim) overrides it
    when a sweep recorded a winner."""
    want = 256
    forced = getattr(_forced_tls, "rows_block", None)
    if forced is not None:
        want = forced
    elif d is not None:
        from ...tuner import lookup
        cfg = lookup("rms_norm", {"d": int(d)}, str(dtype))
        if cfg:
            want = int(cfg.get("block_rows", want))
    return _clamp_rows(want, n_rows, d, forced is not None)


def _pad_rows(a, n_pad):
    if n_pad == a.shape[0]:
        return a
    # explicit-dtype fill: jnp.pad's weak-int 0 re-concretizes as i64
    # under an outer x64-enabled trace and fails interpret lowering
    return jnp.pad(a, ((0, n_pad - a.shape[0]), (0, 0)),
                   constant_values=a.dtype.type(0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, w, eps=1e-6):
    return _rms_fwd_impl(x, w, eps)


def _rms_fwd_impl(x, w, eps):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    blk = _rows_block(n, d, x.dtype)
    n_p = -(-n // blk) * blk  # pad rows to the block multiple
    with _no_x64():
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,))],
            out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, d), x.dtype),
            name="rms_norm_fwd",
            interpret=_interpret(),
        )(_pad_rows(x2, n_p), w)
    return out[:n].reshape(orig_shape)


def _rms_fwd(x, w, eps):
    return _rms_fwd_impl(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    g2 = g.reshape(-1, d)
    n = x2.shape[0]
    blk = _rows_block(n, d, x.dtype)
    n_p = -(-n // blk) * blk
    with _no_x64():
        dx = pl.pallas_call(
            functools.partial(_dx_kernel, eps=eps),
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, d), x.dtype),
            name="rms_norm_bwd",
            interpret=_interpret(),
        )(_pad_rows(x2, n_p), w, _pad_rows(g2, n_p))
    dx = dx[:n]
    # dw: reduction over all rows — XLA's job
    xf = x2.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(ms + eps)
    dw = jnp.sum(g2.astype(jnp.float32) * normed, axis=0).astype(w.dtype)
    return dx.reshape(orig_shape), dw


rms_norm.defvjp(_rms_fwd, _rms_bwd)


# -- tunable surface ---------------------------------------------------------

def _register_rms_surface():
    from ...tuner.surface import TunableSurface, register_surface

    register_surface(TunableSurface(
        name="rms_norm",
        params=("block_rows",),
        default={"block_rows": 256},
        candidates=lambda shape: [{"block_rows": b}
                                  for b in (64, 128, 256, 512, 1024)],
        is_valid=_rows_valid,
        describe="Rows per program of the fused RMSNorm fwd/dx kernels "
                 "(bandwidth-bound VMEM pass). Shape key: feature dim."))


_register_rms_surface()


# ===========================================================================
# Fused RMSNorm + residual (the decoder-layer pair: ``r = x + res;
# y = rmsnorm(r) * w`` in one VMEM pass, both outputs written)
# ===========================================================================


def rms_norm_residual_reference(x, res, w, eps=1e-6):
    """Oracle: residual add in the INPUT dtype (exactly what the
    unfused ``x + res`` followed by ``rms_norm`` computes), then the
    f32 norm — interpret-mode parity tests pin the kernel to this."""
    r = x + res
    rf = r.astype(jnp.float32)
    ms = jnp.mean(jnp.square(rf), axis=-1, keepdims=True)
    y = (rf * jax.lax.rsqrt(ms + eps)).astype(r.dtype) * w
    return y, r


def _fwd_res_kernel(x_ref, res_ref, w_ref, y_ref, r_ref, *, eps):
    # the add happens in the INPUT dtype (bit-parity with the unfused
    # ``x + res``), the norm in f32 — same accumulation discipline as
    # the plain kernel above
    r = x_ref[:] + res_ref[:]
    r_ref[:] = r
    rf = r.astype(jnp.float32)
    ms = jnp.mean(jnp.square(rf), axis=-1, keepdims=True)
    normed = rf * jax.lax.rsqrt(ms + eps)
    y_ref[:] = (normed * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)


def _dres_kernel(x_ref, res_ref, w_ref, gy_ref, gr_ref, o_ref, *, eps):
    # d(x+res) through the norm + the residual-stream grad in one pass:
    # dh = rms_dx(gy) + gr, and dx == dres == dh
    r = (x_ref[:] + res_ref[:]).astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    gy = gy_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(r), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    gw = gy * w
    dot = jnp.mean(gw * r, axis=-1, keepdims=True)
    dh = inv * gw - r * (inv ** 3) * dot + gr_ref[:].astype(jnp.float32)
    o_ref[:] = dh.astype(o_ref.dtype)


class force_residual_rows_block:
    """Context manager pinning the rows-per-program block of the
    residual variant for trials (this thread only)."""

    def __init__(self, block_rows):
        self._val = int(block_rows)

    def __enter__(self):
        self._prev = getattr(_forced_tls, "res_rows_block", None)
        _forced_tls.res_rows_block = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.res_rows_block = self._prev
        return False


def _res_rows_block(n_rows: int, d: int | None = None, dtype=None) -> int:
    """Rows per program for the residual variant ("rms_norm_residual"
    surface — tuned separately from the plain kernel: the extra
    input/output streams shift the VMEM sweet spot)."""
    want = 256
    forced = getattr(_forced_tls, "res_rows_block", None)
    if forced is not None:
        want = forced
    elif d is not None:
        from ...tuner import lookup
        cfg = lookup("rms_norm_residual", {"d": int(d)}, str(dtype))
        if cfg:
            want = int(cfg.get("block_rows", want))
    return _clamp_rows(want, n_rows, d, forced is not None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rms_norm_residual(x, res, w, eps=1e-6):
    """``(rmsnorm(x + res) * w, x + res)`` in one fused pass. Both
    outputs are differentiable (the second feeds the residual stream);
    backward fuses the norm's dx with the residual grad — dx and dres
    are one tensor."""
    return _rms_res_fwd_impl(x, res, w, eps)


def _rms_res_fwd_impl(x, res, w, eps):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    r2 = res.reshape(-1, d)
    n = x2.shape[0]
    blk = _res_rows_block(n, d, x.dtype)
    n_p = -(-n // blk) * blk
    with _no_x64():
        y, r = pl.pallas_call(
            functools.partial(_fwd_res_kernel, eps=eps),
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,))],
            out_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                       pl.BlockSpec((blk, d), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n_p, d), x.dtype),
                       jax.ShapeDtypeStruct((n_p, d), x.dtype)],
            name="rms_norm_residual_fwd",
            interpret=_interpret(),
        )(_pad_rows(x2, n_p), _pad_rows(r2, n_p), w)
    return (y[:n].reshape(orig_shape), r[:n].reshape(orig_shape))


def _rms_res_fwd(x, res, w, eps):
    return _rms_res_fwd_impl(x, res, w, eps), (x, res, w)


def _rms_res_bwd(eps, resids, gs):
    x, res, w = resids
    gy, gr = gs
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    r2 = res.reshape(-1, d)
    gy2 = gy.reshape(-1, d)
    gr2 = gr.reshape(-1, d)
    n = x2.shape[0]
    blk = _res_rows_block(n, d, x.dtype)
    n_p = -(-n // blk) * blk
    with _no_x64():
        dh = pl.pallas_call(
            functools.partial(_dres_kernel, eps=eps),
            grid=(n_p // blk,),
            in_specs=[pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((d,), lambda i: (0,)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0)),
                      pl.BlockSpec((blk, d), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((blk, d), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n_p, d), x.dtype),
            name="rms_norm_residual_bwd",
            interpret=_interpret(),
        )(_pad_rows(x2, n_p), _pad_rows(r2, n_p), w,
          _pad_rows(gy2, n_p), _pad_rows(gr2, n_p))
    dh = dh[:n].reshape(orig_shape)
    # dw: full-rows reduction — XLA's job (same split as the plain bwd)
    hf = (x2 + r2).astype(jnp.float32)
    ms = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
    normed = hf * jax.lax.rsqrt(ms + eps)
    dw = jnp.sum(gy2.astype(jnp.float32) * normed, axis=0).astype(w.dtype)
    return dh, dh, dw


rms_norm_residual.defvjp(_rms_res_fwd, _rms_res_bwd)


def _register_rms_residual_surface():
    from ...tuner.surface import TunableSurface, register_surface

    register_surface(TunableSurface(
        name="rms_norm_residual",
        params=("block_rows",),
        default={"block_rows": 256},
        candidates=lambda shape: [{"block_rows": b}
                                  for b in (64, 128, 256, 512, 1024)],
        is_valid=_rows_valid,
        describe="Rows per program of the fused RMSNorm+residual "
                 "fwd/dh kernels (two streams in, two out — tuned "
                 "separately from plain rms_norm). Shape key: feature "
                 "dim."))


_register_rms_residual_surface()


def rms_norm_cost(x_shape, residual=False, train=False):
    """Static FLOPs/bytes for one (residual-)rmsnorm call (profiler
    cost-accounting surface): x ``[..., d]``. Bandwidth-bound by
    construction — the fused pass reads each stream once and writes
    each output once; the residual variant adds one input and one
    output stream but zero extra passes."""
    import math

    from ...profiler.cost import rms_norm_cost as _cost
    d = int(x_shape[-1])
    n = int(math.prod(int(s) for s in x_shape[:-1]))
    return _cost(n, d, residual=residual, train=train)
