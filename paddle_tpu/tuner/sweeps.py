"""Standalone trial builders for the built-in kernel surfaces.

A *builder* turns a candidate config into a zero-arg callable the
trial engine can time: ``builder(config, shape) -> fn | None``. The
builders here are self-contained (random operands at the requested
shape, fresh ``jax.jit`` per candidate so every candidate compiles its
own variant) and are shared by three consumers:

- the offline CLI (``python -m paddle_tpu.tuner``),
- ``bench.py --autotune`` (sweeps at the bench workload's shapes),
- tune-on-first-call (``incubate.autotune.set_config`` — a cache miss
  for a surface with a builder here triggers one synchronous search).

Surfaces whose trial needs a whole model + workload (``scan_remat``,
``serving_chunks``, ``spec_decode``) have NO standalone builder —
:func:`auto_builder` returns None and the CLI directs users at
``bench.py``, which owns a model (``--autotune``'s cb section sweeps
serving_chunks, its cb-spec section sweeps spec_decode). Their
registered grids/validity still gate what those vehicles may try.

Each trial times forward + backward where the surface has backward
tiles (grouped matmul's ``bd/bh`` only exist in the dw kernel), since
that is the configuration the train hot path runs.
"""

from __future__ import annotations

__all__ = ["ensure_builtin_surfaces", "auto_builder",
           "grouped_matmul_builder", "flash_attention_builder",
           "rms_norm_builder", "ragged_attention_builder",
           "rms_norm_residual_builder", "swiglu_builder",
           "fused_ce_builder", "BENCH_PRESETS"]


def ensure_builtin_surfaces():
    """Import every module that registers a built-in surface (imports
    are the registration mechanism — registrations live next to their
    knobs)."""
    from ..ops import fused_ce  # noqa: F401
    from ..ops.pallas import flash_attention  # noqa: F401
    from ..ops.pallas import grouped_matmul  # noqa: F401
    from ..ops.pallas import ragged_paged_attention  # noqa: F401
    from ..ops.pallas import rms_norm  # noqa: F401
    from ..ops.pallas import swiglu  # noqa: F401
    from ..nn import scan  # noqa: F401
    from ..inference import serving  # noqa: F401


def _trial(step, *operands):
    """Run one trial step with x64 promotion OFF for the whole
    trace+lower+execute: the kernels' internal no_x64 scope covers
    their own trace, but interpret-mode lowering under an outer jit
    happens later — outside it — and mixed i64/i32 loop bounds then
    fail to legalize. Operands carry explicit dtypes, so this changes
    nothing semantically (same argument as ops/pallas/_utils.no_x64)."""
    from ..ops.pallas._utils import no_x64
    with no_x64():
        return step(*operands)


def grouped_matmul_builder(rows=4096, dtype="bfloat16", train=True):
    """Builder for the ``grouped_matmul`` surface: ``rows`` group-
    padded assignment rows through an [E, d, h] bank (shape supplies
    d/h/E), fwd + dx + dw when ``train``."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.grouped_matmul import grouped_matmul
        d, h, E = int(shape["d"]), int(shape["h"]), int(shape["E"])
        bm = 128
        nr = max(int(rows) // bm, E)
        P = nr * bm
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (P, d), jnp.float32).astype(dt)
        w = jax.random.normal(kw, (E, d, h), jnp.float32).astype(dt)
        # contiguous non-decreasing groups, every expert >= 1 tile
        tile_gid = jnp.minimum(
            jnp.arange(nr, dtype=jnp.int32) * E // nr, E - 1)
        bn, bd, bh = (int(config[k]) for k in ("bn", "bd", "bh"))

        if train:
            def loss(x, w):
                return grouped_matmul(x, w, tile_gid, bn=bn, bd=bd,
                                      bh=bh).astype(jnp.float32).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1)))
        else:
            step = jax.jit(lambda x, w: grouped_matmul(
                x, w, tile_gid, bn=bn, bd=bd, bh=bh))
        return lambda: _trial(step, x, w)

    return builder


def flash_attention_builder(batch=1, heads=8, dtype="bfloat16",
                            causal=True, train=True):
    """Builder for the ``flash_attention`` surface (shape supplies
    sq/sk/d). Candidates are pinned through ``force_blocks`` — NOT
    ``set_flags``, which would mark the flags user-explicit and defeat
    the override>cache>default precedence afterwards — with a fresh
    jit per candidate so each traces under its own blocks."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.flash_attention import (flash_attention,
                                                  force_blocks)
        sq, sk, d = int(shape["sq"]), int(shape["sk"]), int(shape["d"])
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (batch, sq, heads, d),
                              jnp.float32).astype(dt)
        k = jax.random.normal(kk, (batch, sk, heads, d),
                              jnp.float32).astype(dt)
        v = jax.random.normal(kv, (batch, sk, heads, d),
                              jnp.float32).astype(dt)
        bq, bkv = int(config["block_q"]), int(config["block_kv"])

        if train:
            def loss(q, k, v):
                return flash_attention(
                    q, k, v, causal).astype(jnp.float32).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        else:
            step = jax.jit(lambda q, k, v: flash_attention(q, k, v,
                                                           causal))

        def fn():
            # the force context must cover the first (tracing) call;
            # later calls hit this candidate's own jit cache
            with force_blocks(bq, bkv):
                return _trial(step, q, k, v)
        return fn

    return builder


def rms_norm_builder(rows=4096, dtype="bfloat16", train=True):
    """Builder for the ``rms_norm`` surface (shape supplies d)."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.rms_norm import force_rows_block, rms_norm
        d = int(shape["d"])
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (int(rows), d),
                              jnp.float32).astype(dt)
        w = jax.random.normal(kw, (d,), jnp.float32).astype(dt)
        blk = int(config["block_rows"])

        if train:
            def loss(x, w):
                return rms_norm(x, w).astype(jnp.float32).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1)))
        else:
            step = jax.jit(rms_norm)

        def fn():
            with force_rows_block(blk):
                return _trial(step, x, w)
        return fn

    return builder


def rms_norm_residual_builder(rows=4096, dtype="bfloat16", train=True):
    """Builder for the ``rms_norm_residual`` surface (shape supplies
    d): the fused residual-add + norm pair, fwd + the fused dh bwd
    when ``train`` — the configuration the decoder hot path runs."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.rms_norm import (force_residual_rows_block,
                                           rms_norm_residual)
        d = int(shape["d"])
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kx, kr, kw = jax.random.split(key, 3)
        x = jax.random.normal(kx, (int(rows), d),
                              jnp.float32).astype(dt)
        r = jax.random.normal(kr, (int(rows), d),
                              jnp.float32).astype(dt)
        w = jax.random.normal(kw, (d,), jnp.float32).astype(dt)
        blk = int(config["block_rows"])

        if train:
            def loss(x, r, w):
                y, rr = rms_norm_residual(x, r, w)
                return (y.astype(jnp.float32).sum()
                        + rr.astype(jnp.float32).sum())
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        else:
            step = jax.jit(lambda x, r, w: rms_norm_residual(x, r, w))

        def fn():
            with force_residual_rows_block(blk):
                return _trial(step, x, r, w)
        return fn

    return builder


def swiglu_builder(rows=4096, dtype="bfloat16", train=True):
    """Builder for the ``swiglu`` surface (shape supplies the
    intermediate dim h)."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.swiglu import force_swiglu_blocks, swiglu_fused
        h = int(shape["h"])
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kg, ku = jax.random.split(key)
        g = jax.random.normal(kg, (int(rows), h),
                              jnp.float32).astype(dt)
        u = jax.random.normal(ku, (int(rows), h),
                              jnp.float32).astype(dt)
        br = int(config["block_rows"])
        bc = int(config["block_cols"])

        if train:
            def loss(g, u):
                return swiglu_fused(g, u).astype(jnp.float32).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1)))
        else:
            step = jax.jit(swiglu_fused)

        def fn():
            with force_swiglu_blocks(br, bc):
                return _trial(step, g, u)
        return fn

    return builder


def fused_ce_builder(rows=4096, dtype="bfloat16", train=True):
    """Builder for the ``fused_ce`` surface (shape supplies d/v): the
    chunked lm_head+CE tail at the train geometry. Candidates pin the
    chunk width through ``force_chunk_v`` (NOT set_flags — that would
    mark FLAGS_fused_ce_chunk_v user-explicit and defeat the
    override > cache > default precedence), fresh jit per candidate."""
    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.fused_ce import (force_chunk_v,
                                    fused_linear_cross_entropy)
        d, v = int(shape["d"]), int(shape["v"])
        n = int(rows)
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(0)
        kh, kw, kl = jax.random.split(key, 3)
        h = jax.random.normal(kh, (n, d), jnp.float32).astype(dt)
        w = (jax.random.normal(kw, (d, v), jnp.float32) * 0.02).astype(dt)
        labels = jax.random.randint(kl, (n,), 0, v, jnp.int32)
        cv = int(config["chunk_v"])

        if train:
            step = jax.jit(jax.grad(
                lambda hh, ww: fused_linear_cross_entropy(hh, ww,
                                                          labels),
                argnums=(0, 1)))
        else:
            step = jax.jit(lambda hh, ww: fused_linear_cross_entropy(
                hh, ww, labels))

        def fn():
            # the force context must cover the first (tracing) call;
            # later calls hit this candidate's own jit cache
            with force_chunk_v(cv):
                return _trial(step, h, w)
        return fn

    return builder


def ragged_attention_builder(slots=8, heads=8, kv_heads=2,
                             dtype="bfloat16"):
    """Builder for the ``ragged_paged_attention`` surface (shape
    supplies c/pages/page/d): a mixed prefill+decode batch — half the
    slots stream a full chunk, half ride one decode token over a deep
    history — through the unified serving kernel. A candidate pins the
    stream tokens a q block and the pages a K/V block through
    ``force_ragged_blocks`` (NOT set_flags, which would defeat the
    override>cache>default precedence) around a jitted function of its
    own: ``_resolve_blocks`` reads the pin outside the kernel's jitted
    call and hands it the blocks as static arguments, so every candidate
    of one shape is a program of its own. The kv heads a program owns
    are not a candidate: every head, as in production."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    def builder(config, shape):
        from ..ops.pallas.ragged_paged_attention import (
            force_ragged_blocks, ragged_paged_attention)
        c = int(shape["c"])
        pages = int(shape["pages"])
        page = int(shape["page"])
        d = int(shape["d"])
        # a "kvq" shape component selects the QUANTIZED kernel variant
        # (int8 data pools + f32 page-parallel scales) — the same
        # component _resolve_blocks keys the cache on, so quantized
        # winners land under a distinct sig from bf16 winners
        quant = bool(shape.get("kvq"))
        dt = jnp.dtype(dtype)
        total = slots * pages + 1      # + the trash page 0
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(
            kq, (slots, c, heads, d), jnp.float32).astype(dt)
        # every token's [kv_heads, d] vectors, then the pool's own shape
        from ..ops.paged_attention import kv_pool_shape, quantize_kv
        pool_shape = kv_pool_shape(kv_heads, total, page, d)
        kp = jax.random.normal(
            kk, (total, page, kv_heads, d), jnp.float32).astype(dt)
        vp = jax.random.normal(
            kv, (total, page, kv_heads, d), jnp.float32).astype(dt)
        ks = vs = None
        if quant:
            (kp, ks), (vp, vs) = (quantize_kv(kp, jnp.int8),
                                  quantize_kv(vp, jnp.int8))
            # [total, page, kv_heads] -> kv_scales_shape
            ks, vs = jnp.swapaxes(ks, 1, 2), jnp.swapaxes(vs, 1, 2)
        kp, vp = kp.reshape(pool_shape), vp.reshape(pool_shape)
        rng = np.random.RandomState(0)
        tables = jnp.asarray(
            (rng.permutation(total - 1)[:slots * pages] + 1)
            .reshape(slots, pages).astype(np.int32))
        # mixed workload: even slots prefill the whole chunk from a
        # shallow ctx, odd slots decode one token over a deep history
        ctx = jnp.asarray([(3 if s % 2 == 0 else pages * page - c - 1)
                           for s in range(slots)], jnp.int32)
        lens = jnp.asarray([(c if s % 2 == 0 else 1)
                            for s in range(slots)], jnp.int32)
        qb = int(config["q_block"])
        g = int(config["kv_pages_per_block"])
        # a function of this candidate's own: jax keeps a trace by the
        # function it traced, so two candidates through one function
        # object would share the first one's program
        def step_fn(qq, kpp, vpp, tb, cx, ln, kss, vss):
            return ragged_paged_attention(
                qq, kpp, vpp, tb, cx, ln, k_scales=kss, v_scales=vss)
        step = jax.jit(step_fn)
        operands = (q, kp, vp, tables, ctx, lens, ks, vs)

        def fn():
            # the force context must cover the first (tracing) call —
            # it short-circuits _resolve_blocks, so the candidate is
            # pinned through the SAME resolution path production uses
            with force_ragged_blocks(qb, g):
                return _trial(step, *operands)
        return fn

    return builder


#: surface -> builder factory taking (dtype) — the tune-on-first-call
#: path and the CLI's default trial hyper-parameters
_AUTO_BUILDERS = {
    "grouped_matmul": lambda dtype: grouped_matmul_builder(dtype=dtype),
    "flash_attention": lambda dtype: flash_attention_builder(dtype=dtype),
    "rms_norm": lambda dtype: rms_norm_builder(dtype=dtype),
    "rms_norm_residual":
        lambda dtype: rms_norm_residual_builder(dtype=dtype),
    "swiglu": lambda dtype: swiglu_builder(dtype=dtype),
    "fused_ce": lambda dtype: fused_ce_builder(dtype=dtype),
    "ragged_paged_attention":
        lambda dtype: ragged_attention_builder(dtype=dtype),
}


def auto_builder(surface_name, dtype="bfloat16"):
    """Standalone builder for ``surface_name``, or None when the
    surface needs a model-level vehicle (scan_remat, serving_chunks)."""
    factory = _AUTO_BUILDERS.get(surface_name)
    return factory(dtype) if factory else None


#: named shape presets for the CLI: the sweep VERDICT r5 demands is
#: one command — `python -m paddle_tpu.tuner --preset moe_bench`.
#: grouped_matmul appears twice because the SwiGLU stack runs two bank
#: orientations: gate/up [E, d, h] and down [E, h, d].
BENCH_PRESETS = {
    "moe_bench": [
        ("grouped_matmul", {"d": 1024, "h": 1408, "E": 16}),
        ("grouped_matmul", {"d": 1408, "h": 1024, "E": 16}),
    ],
    "llama_train": [
        ("flash_attention", {"sq": 2048, "sk": 2048, "d": 128}),
        ("rms_norm", {"d": 2560}),
        # the training-kernel suite at the v5e 2.4B train bench
        # geometry (hidden 2560, intermediate 6912, vocab 32000)
        ("rms_norm_residual", {"d": 2560}),
        ("swiglu", {"h": 6912}),
        ("fused_ce", {"d": 2560, "v": 32000}),
    ],
    "serving": [
        # the v5e llama_1b cb-bench geometry: chunk 32, 12-page rows of
        # 32-token pages, head_dim 128
        ("ragged_paged_attention",
         {"c": 32, "pages": 12, "page": 32, "d": 128}),
        # quantized-KV variant (ISSUE 20): same geometry, int8 pools +
        # f32 scales — "kvq" keys a separate shape sig so bf16 winners
        # can't poison quantized configs (and vice versa)
        ("ragged_paged_attention",
         {"c": 32, "pages": 12, "page": 32, "d": 128, "kvq": 1}),
        # model-level: the CLI points at `bench.py --autotune`'s
        # cb-spec section, which sweeps K x draft source here
        ("spec_decode", {"slots": 1, "max_len": 384, "page": 32}),
    ],
    "cpu_smoke": [
        ("grouped_matmul", {"d": 64, "h": 128, "E": 4}),
        ("flash_attention", {"sq": 128, "sk": 128, "d": 64}),
        ("rms_norm", {"d": 128}),
        ("rms_norm_residual", {"d": 128}),
        ("swiglu", {"h": 256}),
        ("fused_ce", {"d": 64, "v": 1024}),
        ("ragged_paged_attention",
         {"c": 8, "pages": 4, "page": 8, "d": 16}),
        ("ragged_paged_attention",
         {"c": 8, "pages": 4, "page": 8, "d": 16, "kvq": 1}),
        ("spec_decode", {"slots": 1, "max_len": 64, "page": 8}),
    ],
}
