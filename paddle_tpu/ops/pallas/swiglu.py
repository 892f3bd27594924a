"""Fused SwiGLU Pallas kernel — ``silu(gate) * up`` in one VMEM pass.

The unfused functional lowers into sigmoid -> mul -> mul with the
``silu(gate)`` intermediate materialized (and saved for backward) in
HBM; at Llama intermediate sizes that is a full ``[N, H]`` activation
per MLP. The fused kernel reads gate/up once and writes only the
product; the custom VJP saves just the two INPUTS (which the matmuls
that produced them already keep live under dots_saveable remat) and
recomputes sigmoid on-chip in the backward kernel — dgate and dup come
out of one fused pass.

Same discipline as flash_attention/rms_norm: interpret mode everywhere
but TPU (the kernel path is what tests exercise), thread-local force
hook for tuner trials, tile sizes registered as the ``swiglu`` tunable
surface next to the knob.
"""

from __future__ import annotations

import functools
import threading as _threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode as _interpret, no_x64 as _no_x64

__all__ = ["swiglu_fused", "swiglu_reference", "swiglu_cost",
           "force_swiglu_blocks"]


def swiglu_reference(gate, up):
    """Oracle: ``jax.nn.silu(gate) * up`` — exactly the unfused
    functional's math (silu computed in the input dtype)."""
    return jax.nn.silu(gate) * up


def _fwd_kernel(g_ref, u_ref, o_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    o_ref[:] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _bwd_kernel(g_ref, u_ref, go_ref, dg_ref, du_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    go = go_ref[:].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    # d silu(g) = sig * (1 + g * (1 - sig)). The literal is explicit
    # f32: weak python floats re-concretize as f64 when the interpret-
    # mode jaxpr lowers under an outer x64-enabled trace.
    one = jnp.float32(1.0)
    dg_ref[:] = (go * u * sig * (one + g * (one - sig))).astype(
        dg_ref.dtype)
    du_ref[:] = (go * silu).astype(du_ref.dtype)


_forced_tls = _threading.local()


class force_swiglu_blocks:
    """Context manager pinning (block_rows, block_cols) for trials
    (this thread only) — same contract as flash_attention.force_blocks."""

    def __init__(self, block_rows, block_cols):
        self._val = (int(block_rows), int(block_cols))

    def __enter__(self):
        self._prev = getattr(_forced_tls, "blocks", None)
        _forced_tls.blocks = self._val
        return self

    def __exit__(self, *exc):
        _forced_tls.blocks = self._prev
        return False


def _blocks(n_rows: int, h: int, dtype=None) -> tuple[int, int]:
    """(rows, cols) per program. 256x1024 is the static pick; the
    tuner cache ("swiglu" surface, keyed by the intermediate dim)
    overrides it when a sweep recorded a winner."""
    want = (256, 1024)
    forced = getattr(_forced_tls, "blocks", None)
    if forced is not None:
        want = forced
    else:
        from ...tuner import lookup
        cfg = lookup("swiglu", {"h": int(h)}, str(dtype))
        if cfg:
            want = (int(cfg.get("block_rows", want[0])),
                    int(cfg.get("block_cols", want[1])))
    br = min(want[0], -(-n_rows // 8) * 8)
    bc = min(want[1], -(-h // 128) * 128)
    return br, bc


def _pad2(a, n_pad, h_pad):
    if n_pad == a.shape[0] and h_pad == a.shape[1]:
        return a
    # explicit-dtype fill: jnp.pad's weak-int 0 re-concretizes as i64
    # under an outer x64-enabled trace and fails interpret lowering
    return jnp.pad(a, ((0, n_pad - a.shape[0]), (0, h_pad - a.shape[1])),
                   constant_values=a.dtype.type(0))


@jax.custom_vjp
def swiglu_fused(gate, up):
    """Fused ``silu(gate) * up``; any leading shape, elementwise over
    the last dim. Backward is one fused dgate/dup kernel from the raw
    inputs (no silu intermediate ever saved)."""
    return _swiglu_fwd_impl(gate, up)


def _swiglu_fwd_impl(gate, up):
    orig_shape = gate.shape
    h = orig_shape[-1]
    g2 = gate.reshape(-1, h)
    u2 = up.reshape(-1, h)
    n = g2.shape[0]
    br, bc = _blocks(n, h, gate.dtype)
    n_p = -(-n // br) * br
    h_p = -(-h // bc) * bc
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    with _no_x64():
        out = pl.pallas_call(
            _fwd_kernel,
            grid=(n_p // br, h_p // bc),
            in_specs=[spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((n_p, h_p), gate.dtype),
            name="swiglu_fwd",
            interpret=_interpret(),
        )(_pad2(g2, n_p, h_p), _pad2(u2, n_p, h_p))
    return out[:n, :h].reshape(orig_shape)


def _swiglu_fwd(gate, up):
    return _swiglu_fwd_impl(gate, up), (gate, up)


def _swiglu_bwd(resids, go):
    gate, up = resids
    orig_shape = gate.shape
    h = orig_shape[-1]
    g2 = gate.reshape(-1, h)
    u2 = up.reshape(-1, h)
    go2 = go.reshape(-1, h)
    n = g2.shape[0]
    br, bc = _blocks(n, h, gate.dtype)
    n_p = -(-n // br) * br
    h_p = -(-h // bc) * bc
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    with _no_x64():
        dg, du = pl.pallas_call(
            _bwd_kernel,
            grid=(n_p // br, h_p // bc),
            in_specs=[spec, spec, spec],
            out_specs=[spec, spec],
            out_shape=[jax.ShapeDtypeStruct((n_p, h_p), gate.dtype),
                       jax.ShapeDtypeStruct((n_p, h_p), up.dtype)],
            name="swiglu_bwd",
            interpret=_interpret(),
        )(_pad2(g2, n_p, h_p), _pad2(u2, n_p, h_p),
          _pad2(go2, n_p, h_p))
    return (dg[:n, :h].reshape(orig_shape),
            du[:n, :h].reshape(orig_shape))


swiglu_fused.defvjp(_swiglu_fwd, _swiglu_bwd)


# -- tunable surface ---------------------------------------------------------

def _register_swiglu_surface():
    from ...tuner.surface import TunableSurface, register_surface

    register_surface(TunableSurface(
        name="swiglu",
        params=("block_rows", "block_cols"),
        default={"block_rows": 256, "block_cols": 1024},
        candidates=lambda shape: [
            {"block_rows": br, "block_cols": bc}
            for br in (128, 256, 512)
            for bc in (512, 1024, 2048)],
        is_valid=lambda config, shape: (
            config["block_rows"] % 8 == 0
            and config["block_cols"] % 128 == 0
            # bwd holds 5 blocks (g, u, go, dg, du) live in VMEM
            and 5 * config["block_rows"] * config["block_cols"] * 4
            <= 12 * 1024 * 1024),
        describe="Fused SwiGLU (rows x cols) tile of the fwd and the "
                 "dgate/dup bwd kernels (pure VPU, bandwidth-bound). "
                 "Shape key: intermediate dim h."))


_register_swiglu_surface()


def swiglu_cost(shape, train=False):
    """Static FLOPs/bytes for one fused swiglu over ``[..., h]``
    (profiler cost-accounting surface)."""
    import math

    from ...profiler.cost import swiglu_cost as _cost
    h = int(shape[-1])
    n = int(math.prod(int(s) for s in shape[:-1]))
    return _cost(n, h, train=train)
