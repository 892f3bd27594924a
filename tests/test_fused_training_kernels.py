"""ISSUE-8 tentpole: the fused training-kernel suite.

Interpret-mode kernel-vs-oracle parity + gradient checks for the fused
training kernels (fused RMSNorm+residual, fused SwiGLU, the fused head +
cross-entropy kernels), the fused decoder wiring's bit-parity against
the unfused stack, the labeled forwards' fused loss tail against the
cross-entropy over their logits, and the compiled fit's labeled loss
against the eager criterion-over-logits oracle at pinned rtol.

The ``fused_parity`` marker selects the kernel-parity subset the
``tools/run_gates.py fused_parity`` gate runs with fused flags forced
on (FLAGS_* env vars); on CPU every kernel executes in interpret mode
— the kernel path itself is what is being checked, not an XLA
fallback.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework import flags


@pytest.fixture
def flag_guard():
    """Snapshot/restore the fused-suite flags (value AND source) so a
    test's set_flags can't leak user-explicit state into the session."""
    names = ["FLAGS_fused_rmsnorm_residual", "FLAGS_fused_swiglu"]
    saved = {n: dict(flags._registry[n]) for n in names}
    yield flags
    for n, ent in saved.items():
        flags._registry[n] = ent


# ===========================================================================
# fused RMSNorm + residual kernel
# ===========================================================================


@pytest.mark.fused_parity
class TestRmsNormResidualKernel:
    def _data(self, n, d, dtype="float32", seed=0):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(n, d).astype("float32")).astype(dtype)
        r = jnp.asarray(rng.randn(n, d).astype("float32")).astype(dtype)
        w = jnp.asarray(rng.randn(d).astype("float32")).astype(dtype)
        return x, r, w

    @pytest.mark.parametrize("n,d,blk", [
        (32, 24, 16),      # dividing
        (37, 24, 16),      # rows not a block multiple
        (5, 16, 64),       # block larger than the rows
    ])
    def test_fwd_matches_reference(self, n, d, blk):
        from paddle_tpu.ops.pallas.rms_norm import (
            force_residual_rows_block, rms_norm_residual,
            rms_norm_residual_reference)
        x, r, w = self._data(n, d)
        with force_residual_rows_block(blk):
            y, rr = rms_norm_residual(x, r, w)
        yr, rref = rms_norm_residual_reference(x, r, w)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-6)
        # the residual-stream output is an exact add
        np.testing.assert_array_equal(np.asarray(rr), np.asarray(rref))

    def test_grads_match_reference_both_outputs(self):
        """dx/dres/dw through BOTH outputs (y feeds the block, r feeds
        the residual stream — the fused bwd must combine them)."""
        from paddle_tpu.ops.pallas.rms_norm import (
            force_residual_rows_block, rms_norm_residual,
            rms_norm_residual_reference)
        x, r, w = self._data(37, 24, seed=1)

        def scalar(fn):
            def f(x, r, w):
                y, rr = fn(x, r, w)
                return (jnp.sum(y * jnp.cos(y))
                        + jnp.sum(rr * jnp.sin(rr)))
            return f

        with force_residual_rows_block(16):
            gk = jax.grad(scalar(rms_norm_residual),
                          argnums=(0, 1, 2))(x, r, w)
        gr = jax.grad(scalar(rms_norm_residual_reference),
                      argnums=(0, 1, 2))(x, r, w)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_bf16_inputs_fp32_accum(self):
        """bf16 in/out with f32 kernel accumulation: the kernel must sit
        within bf16 resolution of the f32 oracle, not of a bf16-math
        recomputation."""
        from paddle_tpu.ops.pallas.rms_norm import (
            force_residual_rows_block, rms_norm_residual)
        x, r, w = self._data(33, 32, dtype=jnp.bfloat16, seed=2)
        with force_residual_rows_block(8):
            y, rr = rms_norm_residual(x, r, w)
        assert y.dtype == jnp.bfloat16 and rr.dtype == jnp.bfloat16
        hf = (x + r).astype(jnp.float32)
        ms = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
        yf = (hf * jax.lax.rsqrt(ms + 1e-6)).astype(jnp.bfloat16) \
            * w
        np.testing.assert_allclose(
            np.asarray(y, dtype=np.float32),
            np.asarray(yf, dtype=np.float32), rtol=2e-2, atol=2e-2)


# ===========================================================================
# fused SwiGLU kernel
# ===========================================================================


@pytest.mark.fused_parity
class TestSwigluKernel:
    def _data(self, n, h, dtype="float32", seed=0):
        rng = np.random.RandomState(seed)
        g = jnp.asarray(rng.randn(n, h).astype("float32")).astype(dtype)
        u = jnp.asarray(rng.randn(n, h).astype("float32")).astype(dtype)
        return g, u

    @pytest.mark.parametrize("n,h,br,bc", [
        (16, 256, 8, 128),     # dividing
        (13, 200, 8, 128),     # neither rows nor cols divide the tile
        (3, 64, 64, 512),      # tiles larger than the operand
    ])
    def test_fwd_matches_reference(self, n, h, br, bc):
        from paddle_tpu.ops.pallas.swiglu import (force_swiglu_blocks,
                                                  swiglu_fused,
                                                  swiglu_reference)
        g, u = self._data(n, h)
        with force_swiglu_blocks(br, bc):
            out = swiglu_fused(g, u)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(swiglu_reference(g, u)),
                                   rtol=1e-5, atol=1e-6)

    def test_grads_match_reference(self):
        from paddle_tpu.ops.pallas.swiglu import (force_swiglu_blocks,
                                                  swiglu_fused,
                                                  swiglu_reference)
        g, u = self._data(13, 200, seed=1)
        with force_swiglu_blocks(8, 128):
            gk = jax.grad(lambda a, b: jnp.sum(jnp.tanh(
                swiglu_fused(a, b))), argnums=(0, 1))(g, u)
        gr = jax.grad(lambda a, b: jnp.sum(jnp.tanh(
            swiglu_reference(a, b))), argnums=(0, 1))(g, u)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_bf16_inputs_fp32_accum(self):
        from paddle_tpu.ops.pallas.swiglu import (force_swiglu_blocks,
                                                  swiglu_fused)
        g, u = self._data(17, 160, dtype=jnp.bfloat16, seed=2)
        with force_swiglu_blocks(8, 128):
            out = swiglu_fused(g, u)
        assert out.dtype == jnp.bfloat16
        ref = (g.astype(jnp.float32) * jax.nn.sigmoid(
            g.astype(jnp.float32)) * u.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref), rtol=2e-2,
                                   atol=2e-2)

    def test_leading_batch_dims(self):
        from paddle_tpu.ops.pallas.swiglu import swiglu_fused, \
            swiglu_reference
        g, u = self._data(24, 32)
        g3, u3 = g.reshape(2, 12, 32), u.reshape(2, 12, 32)
        np.testing.assert_allclose(
            np.asarray(swiglu_fused(g3, u3)),
            np.asarray(swiglu_reference(g3, u3)), rtol=1e-5, atol=1e-6)


# ===========================================================================
# fused head + cross-entropy: the TPU kernels (interpreted here) and the
# jnp scan, both against the plain CE over materialized logits
# ===========================================================================


def _plain_ce(h, w, labels, ignore_index=-100, vocab_first=False):
    logits = h @ (w.T if vocab_first else w)
    lp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    per = -jnp.take_along_axis(lp, safe[:, None], -1)[:, 0]
    return jnp.sum(jnp.where(valid, per, 0.0)) \
        / jnp.maximum(jnp.sum(valid), 1)


def _ce_path(monkeypatch, path, tiles):
    """Steer ``ops.fused_ce`` to one of its two implementations: the
    kernels (interpreted off the TPU) at ``tiles`` = (rows, vocab), or
    the jnp scan at ``tiles`` vocab columns a chunk."""
    from paddle_tpu.ops import fused_ce
    from paddle_tpu.ops.pallas import linear_ce
    if path == "kernel":
        monkeypatch.setattr(fused_ce, "_use_kernel", lambda: True)
        monkeypatch.setattr(linear_ce, "_ROW_TILE", tiles[0])
        monkeypatch.setattr(linear_ce, "_VOCAB_TILE", tiles[1])
    else:
        monkeypatch.setattr(fused_ce, "_CHUNK_V", tiles)


@pytest.mark.fused_parity
class TestFusedCE:
    def _data(self, n=24, d=16, v=50, vocab_first=False, seed=0):
        rng = np.random.RandomState(seed)
        h = jnp.asarray(rng.randn(n, d).astype("float32"))
        w = jnp.asarray(rng.randn(*((v, d) if vocab_first else (d, v)))
                        .astype("float32") * 0.1)
        labels = jnp.asarray(rng.randint(0, v, (n,)).astype("int32"))
        return h, w, labels

    @pytest.mark.parametrize("layout", ["dv", "vd"])
    @pytest.mark.parametrize("path,n,v,tiles", [
        ("scan", 24, 50, 8),      # V % chunk != 0: clamped tail overlaps
        ("scan", 24, 48, 8),      # dividing
        ("scan", 24, 50, 1024),   # one chunk wider than the vocab
        ("kernel", 37, 300, (16, 128)),   # neither N nor V divides a tile
        ("kernel", 32, 256, (16, 128)),   # dividing
        ("kernel", 24, 50, (1024, 512)),  # one tile larger than both
    ])
    def test_parity_and_grads(self, monkeypatch, layout, path, n, v, tiles):
        """Loss + dh/dW parity against the plain CE, a target in the last
        vocab column (the tail tile / the overlap), one ignored row, the
        weight in either layout: ``[D, V]`` or ``[V, D]`` (a tied
        embedding), dW returned in it."""
        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        vf = layout == "vd"
        h, w, labels = self._data(n=n, v=v, vocab_first=vf)
        labels = labels.at[3].set(-100).at[0].set(v - 1)
        _ce_path(monkeypatch, path, tiles)

        def fused(a, b):
            return fused_linear_cross_entropy(a, b, labels, -100, vf)

        def plain(a, b):
            return _plain_ce(a, b, labels, vocab_first=vf)

        np.testing.assert_allclose(float(fused(h, w)), float(plain(h, w)),
                                   rtol=1e-5)
        g = jax.jit(jax.grad(fused, argnums=(0, 1)))(h, w)
        g_ref = jax.grad(plain, argnums=(0, 1))(h, w)
        assert g[1].shape == w.shape
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    @pytest.mark.parametrize("path,tiles", [("scan", 8),
                                            ("kernel", (16, 128))])
    def test_all_ignored_rows_zero_not_nan(self, monkeypatch, path, tiles):
        """An ignore_index-heavy batch degrading to ALL-masked must
        yield loss exactly 0 and zero (not NaN) grads."""
        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        h, w, _ = self._data(v=200)
        labels = jnp.full((h.shape[0],), -100, jnp.int32)
        _ce_path(monkeypatch, path, tiles)
        assert float(fused_linear_cross_entropy(h, w, labels)) == 0.0
        g = jax.grad(
            lambda a, b: fused_linear_cross_entropy(a, b, labels),
            argnums=(0, 1))(h, w)
        for t in g:
            arr = np.asarray(t)
            assert not np.isnan(arr).any()
            assert np.abs(arr).max() == 0.0

    def test_mostly_ignored_batch(self, monkeypatch):
        """ignore-heavy (not fully masked): mean over the 2 surviving
        rows only."""
        from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy
        h, w, labels = self._data()
        mask = np.full(h.shape[0], True)
        mask[[4, 9]] = False
        labels = jnp.where(jnp.asarray(mask), -100, labels)
        _ce_path(monkeypatch, "scan", 8)
        out = float(fused_linear_cross_entropy(h, w, labels))
        np.testing.assert_allclose(out, float(_plain_ce(h, w, labels)),
                                   rtol=1e-5)

    def test_tiles_follow_the_shape(self):
        """ONE rule gives both kernels their tiles: the largest tiles at
        a training cell's shape, a small operand's rounded up to the
        (16, 128) bf16 tiling."""
        from paddle_tpu.ops.pallas import linear_ce as K
        assert K._resolve_tiles(4092, 50257) == (K._ROW_TILE,
                                                 K._VOCAB_TILE)
        assert K._resolve_tiles(37, 300) == (min(48, K._ROW_TILE),
                                             min(384, K._VOCAB_TILE))
        for n, v in [(1, 1), (37, 300), (4092, 50257), (8192, 152064)]:
            tm, tv = K._resolve_tiles(n, v)
            assert tm % 16 == 0 and tv % 128 == 0


# ===========================================================================
# the labeled training forwards: the fused tail against the CE over the
# unlabeled forward's logits
# ===========================================================================


def _labeled_model(kind):
    from paddle_tpu.models import (DeepseekV2Config, DeepseekV2ForCausalLM,
                                   GPT2Config, GPT2ForCausalLM,
                                   LlamaConfig, LlamaForCausalLM,
                                   Qwen2Config, Qwen2ForCausalLM)
    paddle.seed(0)
    if kind == "gpt2":
        return GPT2ForCausalLM(GPT2Config.tiny())
    if kind == "qwen2":
        cfg = Qwen2Config.tiny()
        cfg.tensor_parallel = False
        return Qwen2ForCausalLM(cfg)
    if kind == "deepseek":
        cfg = DeepseekV2Config.tiny()
        cfg.tensor_parallel = False
        cfg.router_aux_loss_coef = 0.0     # the unlabeled forward has none
        return DeepseekV2ForCausalLM(cfg)
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.tie_word_embeddings = kind == "llama_tied"
    return LlamaForCausalLM(cfg)


@pytest.mark.parametrize("path,tiles", [("scan", 1024),
                                        ("kernel", (16, 128))])
@pytest.mark.parametrize("kind", ["gpt2", "llama", "llama_tied", "qwen2",
                                  "deepseek"])
def test_labeled_forward_matches_ce_over_logits(monkeypatch, kind, path,
                                                tiles):
    """``model(ids, labels=ids)`` is ``(None, loss)``: its loss equals
    ``F.cross_entropy`` over the shifted logits of ``model(ids)`` (1e-5)
    and so do its gradients (1e-4), in f32 — the tied head ``[V, D]``
    (GPT-2's ``wte``, a tied Llama) and the untied ``[D, V]`` alike."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.ops import manipulation as M
    m = _labeled_model(kind)
    v = m.config.vocab_size
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, v, (2, 16)).astype("int64"))
    _ce_path(monkeypatch, path, tiles)

    def grads(loss):
        loss.backward()
        out = {n: np.asarray(p.grad._data).copy()
               for n, p in m.named_parameters() if p.grad is not None}
        for p in m.parameters():
            p.clear_grad()
        return out

    none, loss = m(ids, labels=ids)
    assert none is None
    g = grads(loss)
    logits = m(ids)
    ref = F.cross_entropy(M.reshape(logits[:, :-1, :], [-1, v]),
                          M.reshape(ids[:, 1:], [-1]))
    g_ref = grads(ref)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
    assert set(g) == set(g_ref) and len(g) > 0
    for n in g:
        np.testing.assert_allclose(g[n], g_ref[n], rtol=1e-4, atol=1e-6,
                                   err_msg=n)


# ===========================================================================
# fused decoder wiring (models) — bit-parity against the unfused stack
# ===========================================================================


class TestFusedDecoderWiring:
    def _llama(self, **over):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        for k, v in over.items():
            setattr(cfg, k, v)
        paddle.seed(0)
        return LlamaForCausalLM(cfg), cfg

    def _ids(self, cfg, n=2, s=16):
        return paddle.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (n, s)).astype("int64"))

    def test_llama_fused_carry_bit_parity(self, flag_guard):
        """The (hidden, residual) carry re-associates only commutative
        adds — on CPU (jnp pairing) loss must be BIT-identical and
        grads allclose vs the plain stack."""
        m, cfg = self._llama()
        ids = self._ids(cfg)
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": True})
        _, lf = m(ids, labels=ids)
        lf.backward()
        gf = {n: np.asarray(p.grad._data).copy()
              for n, p in m.named_parameters() if p.grad is not None}
        for p in m.parameters():
            p.clear_grad()
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": False})
        _, lp = m(ids, labels=ids)
        lp.backward()
        gp = {n: np.asarray(p.grad._data).copy()
              for n, p in m.named_parameters() if p.grad is not None}
        assert float(lf) == float(lp)
        assert set(gf) == set(gp) and len(gf) > 0
        for n in gf:
            np.testing.assert_allclose(gf[n], gp[n], rtol=1e-5,
                                       atol=1e-7, err_msg=n)

    # breadth beyond the first variant rides the slow tier (fast-gate
    # budget discipline); core_attn interval 1 — the bench config's
    # shape — stays in tier-1
    @pytest.mark.parametrize("gran,interval", [
        ("core_attn", 1),
        pytest.param("full", 1, marks=pytest.mark.slow),
        pytest.param("core_attn", 2, marks=pytest.mark.slow)])
    def test_llama_fused_remat_variants(self, flag_guard, gran,
                                        interval):
        """Backward recompute must run THROUGH the fused kernels: every
        remat flavor keeps loss bit-parity and full grad coverage."""
        m, cfg = self._llama(use_recompute=True,
                             recompute_granularity=gran,
                             core_attn_interval=interval)
        m.train()
        ids = self._ids(cfg)
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": True})
        _, lf = m(ids, labels=ids)
        lf.backward()
        n_grads = sum(1 for p in m.parameters() if p.grad is not None)
        assert n_grads == len(list(m.parameters()))
        for p in m.parameters():
            p.clear_grad()
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": False})
        _, lp = m(ids, labels=ids)
        assert float(lf) == float(lp)

    def test_qwen2_fused_pair_parity(self, flag_guard):
        from paddle_tpu.models.qwen2 import Qwen2Config, \
            Qwen2ForCausalLM
        cfg = Qwen2Config.tiny() if hasattr(Qwen2Config, "tiny") else \
            Qwen2Config(vocab_size=128, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=4,
                        num_key_value_heads=2, intermediate_size=64,
                        max_position_embeddings=64)
        cfg.tensor_parallel = False
        paddle.seed(0)
        m = Qwen2ForCausalLM(cfg)
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 12)).astype("int64"))
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": True})
        out_f = m(ids)
        flag_guard.set_flags({"FLAGS_fused_rmsnorm_residual": False})
        out_p = m(ids)
        lf = out_f[0] if isinstance(out_f, tuple) else out_f
        lp = out_p[0] if isinstance(out_p, tuple) else out_p
        np.testing.assert_array_equal(np.asarray(lf._data),
                                      np.asarray(lp._data))


# ===========================================================================
# compiled fit: the network's labeled loss vs the eager criterion oracle
# ===========================================================================


class TestFitFusedCE:
    def _model(self, certified=True):
        from paddle_tpu.hapi import Model
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        paddle.seed(0)
        net = LlamaForCausalLM(cfg)
        m = Model(net)
        crit = LlamaPretrainingCriterion(cfg)
        if not certified:
            crit.fuses_with_network_loss = False
        m.prepare(paddle.optimizer.SGD(1e-4,
                                       parameters=net.parameters()),
                  crit)
        return m, cfg

    def _ds(self, cfg, rows=8, s=32):
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (rows, s + 1)).astype("int64"))
        return paddle.io.TensorDataset([ids, ids])

    def _spy(self, monkeypatch):
        from paddle_tpu.ops import fused_ce as fmod
        calls = {"n": 0}
        real = fmod.fused_linear_cross_entropy

        def spy(*args, **kw):
            calls["n"] += 1
            return real(*args, **kw)

        monkeypatch.setattr(fmod, "fused_linear_cross_entropy", spy)
        return calls

    def test_compiled_fused_matches_eager_unfused_oracle(self, monkeypatch):
        """The acceptance pin: fit(compiled=True) — which routes labels
        into the network, so its fused head + CE tail runs — must match
        fit(compiled=False)'s eager criterion-over-logits loop at rtol
        1e-5, and the fused op must actually have run (spy)."""
        calls = self._spy(monkeypatch)
        m, cfg = self._model()
        m.fit(self._ds(cfg), batch_size=4, epochs=1, verbose=0,
              shuffle=False, log_freq=1_000_000)
        fused = m._last_epoch_summary
        assert calls["n"] > 0, "fused linear+CE never engaged"
        calls["n"] = 0

        m2, cfg2 = self._model()          # fresh model, same seed
        m2.fit(self._ds(cfg2), batch_size=4, epochs=1, verbose=0,
               shuffle=False, log_freq=1_000_000, compiled=False)
        eager = m2._last_epoch_summary
        assert calls["n"] == 0            # the oracle runs the criterion
        np.testing.assert_allclose(fused["mean_loss"],
                                   eager["mean_loss"], rtol=1e-5)

    @pytest.mark.slow
    def test_uncertified_criterion_runs_over_logits(self, monkeypatch):
        """A criterion that does not certify ``fuses_with_network_loss``
        is what the compiled step runs: over the unlabeled forward's
        logits, never through the network's labeled tail."""
        calls = self._spy(monkeypatch)
        m, cfg = self._model(certified=False)
        m.fit(self._ds(cfg), batch_size=4, epochs=1, verbose=0,
              shuffle=False, log_freq=1_000_000)
        assert calls["n"] == 0


# ===========================================================================
# tunable surfaces, sweep builders, cost estimators
# ===========================================================================


class TestSurfacesAndCosts:
    def test_surfaces_registered_with_valid_grids(self):
        from paddle_tpu.tuner import sweeps
        from paddle_tpu.tuner.surface import get_surface
        sweeps.ensure_builtin_surfaces()
        for name, shape in [("rms_norm_residual", {"d": 128}),
                            ("swiglu", {"h": 256})]:
            s = get_surface(name)
            grid = s.grid(shape)
            assert grid and grid[0] == s.default
            assert all(s.is_valid(c, shape) for c in grid)

    @pytest.mark.slow
    def test_builders_produce_runnable_trials(self):
        from paddle_tpu.tuner import sweeps
        jobs = [
            (sweeps.rms_norm_residual_builder(rows=64,
                                              dtype="float32"),
             {"block_rows": 16}, {"d": 32}),
            (sweeps.swiglu_builder(rows=64, dtype="float32"),
             {"block_rows": 16, "block_cols": 128}, {"h": 128}),
        ]
        for builder, config, shape in jobs:
            fn = builder(config, shape)
            assert fn is not None
            fn()      # one trial step must run (grads included)

    def test_cost_estimators(self):
        from paddle_tpu.ops.fused_ce import fused_ce_cost
        from paddle_tpu.ops.pallas.rms_norm import rms_norm_cost
        from paddle_tpu.ops.pallas.swiglu import swiglu_cost
        c = fused_ce_cost(4096, 2560, 32000)
        ct = fused_ce_cost(4096, 2560, 32000, train=True)
        assert c.flops > 0 and c.bytes > 0
        assert ct.flops == pytest.approx(3 * c.flops)
        # the whole point: bytes are the h/w operand streams plus [N]
        # vectors — never an [N, V] logits buffer (which alone would
        # add 4*N*V on top)
        streams = 2 * (4096 * 2560 + 2560 * 32000)
        assert c.bytes < streams + 64 * 4096
        assert c.bytes + 4 * 4096 * 32000 > 2 * c.bytes
        r = rms_norm_cost((512, 2560), residual=True)
        r0 = rms_norm_cost((512, 2560), residual=False)
        assert r.flops > r0.flops and r.bytes > r0.bytes
        s = swiglu_cost((512, 6912))
        st = swiglu_cost((512, 6912), train=True)
        assert s.flops > 0 and st.flops == pytest.approx(3 * s.flops)
