"""Mellum 2 (window layers beside YaRN full layers, softmax-routed
held-share experts) against its plain reference (perfbench/reference/
mellum2.py) at the tiny preset, seeded float32 weights: the labeled loss and
every parameter's gradient, with and without per-layer recomputation; two
steps through ``to_static`` + AdamW; the expert counters summed on the device
and booked once; and the YaRN frequencies against an independent
transcription of the formula."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import MellumConfig, MellumForCausalLM  # noqa: E402
from paddle_tpu.ops import moe  # noqa: E402
from paddle_tpu.ops.pallas.rope import rope_inv_freq  # noqa: E402
from perfbench.harness import weights  # noqa: E402
from perfbench.reference import mellum2 as R  # noqa: E402

STD = 0.05
_SIZE_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "sliding_window", "rope_parameters", "rms_norm_eps",
              "num_experts_per_tok", "moe_intermediate_size",
              "norm_topk_prob", "router_aux_loss_coef")


def sizes(cfg):
    """The reference's view of a program config: the experts held under
    ``num_experts``, the router's width apart."""
    m = {k: getattr(cfg, k) for k in _SIZE_KEYS}
    m["layer_types"] = list(cfg.attention_kinds)
    m["router_num_experts"] = cfg.num_experts
    m["first_held_expert"], m["num_experts"] = cfg.held
    return m


def tiny_cfg(**kw):
    cfg = MellumConfig.tiny()
    cfg.num_experts_held, cfg.first_held_expert = 8, 4
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


def seeded(cfg, seed):
    m = sizes(cfg)
    specs = R.param_specs(m)
    paddle.seed(0)
    model = MellumForCausalLM(cfg)
    model.train()
    named = list(model.named_parameters())
    assert [(n, tuple(p.shape)) for n, p in named] \
        == [(n, tuple(s)) for n, s, _ in specs]
    for (_, p), a in zip(named, weights.make_all(specs, seed, STD,
                                                 jnp.float32)):
        p.set_data(a)
    w = weights.LeafSource(specs, weights.seed_words(seed), STD, jnp.float32,
                           R.LAYER_PATTERN).all()
    return model, m, w


def _ids(m, b=2, s=40, seed=0):
    return np.random.RandomState(seed).randint(
        0, m["vocab_size"], (b, s)).astype(np.int32)


def _program_grads(model, ids):
    _, loss = model(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    loss.backward()
    return float(loss.numpy()), {n: np.asarray(p.grad.numpy())
                                 for n, p in model.named_parameters()}


def _reference_grads(m, w, ids):
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(
            lambda ww: R.loss_fn(m, ww, jnp.asarray(ids)))(w)
    return float(loss), {n: np.asarray(a) for n, a in g.items()}


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["stored", "recomputed"])
def test_loss_and_every_gradient_match_the_reference(recompute):
    model, m, w = seeded(tiny_cfg(recompute=recompute), 3)
    ids = _ids(m)
    loss, grads = _program_grads(model, ids)
    ref_loss, ref = _reference_grads(m, w, ids)
    assert loss == pytest.approx(ref_loss, rel=1e-5)
    for n, g in grads.items():
        scale = max(np.abs(ref[n]).max(), 1e-12)
        assert np.abs(g - ref[n]).max() <= 1e-4 * scale, n


def test_the_loss_holds_the_router_aux_term():
    """Without the coefficient the loss is the cross entropy alone; the
    term is E x sum f_e P_e, about k under an even router."""
    model, m, w = seeded(tiny_cfg(), 5)
    ids = _ids(m, seed=1)
    _, with_aux = model(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    model.config.router_aux_loss_coef = 0.0
    _, ce = model(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    aux = (float(with_aux.numpy()) - float(ce.numpy())) / 0.001
    k = m["num_experts_per_tok"]
    assert 0.9 * k < aux < 1.6 * k


def test_a_window_layer_sees_only_its_window():
    """A token more than a window back reaches the output of a window
    layer only through the full layer: with every layer a window layer, the
    last position's logits do not move when the first token changes."""
    cfg = tiny_cfg(num_hidden_layers=2,
                   layer_types=("sliding_attention",) * 2)
    model, m, _ = seeded(cfg, 7)
    model.eval()
    ids = _ids(m, b=1, s=32, seed=2)
    other = ids.copy()
    other[0, 0] = (other[0, 0] + 1) % m["vocab_size"]
    a = model(paddle.to_tensor(ids)).numpy()[0, -1]
    b = model(paddle.to_tensor(other)).numpy()[0, -1]
    assert np.array_equal(a, b)
    cfg.layer_types = ("sliding_attention", "full_attention")
    model, _, _ = seeded(cfg, 7)
    model.eval()
    a = model(paddle.to_tensor(ids)).numpy()[0, -1]
    b = model(paddle.to_tensor(other)).numpy()[0, -1]
    assert not np.array_equal(a, b)


def test_two_adamw_steps_through_to_static():
    """The normal training path: a ``to_static`` step (eager discovery,
    then the compiled program) with AdamW and a global-norm clip, against
    the reference's two steps: losses and the weights after them."""
    from paddle_tpu import nn
    o = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
         "epsilon": 1e-8, "weight_decay": 0.01, "grad_clip_global_norm": 1.0}
    model, m, w0 = seeded(tiny_cfg(recompute=True), 11)
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"],
        beta2=o["beta2"], epsilon=o["epsilon"],
        parameters=model.parameters(), weight_decay=o["weight_decay"],
        grad_clip=nn.ClipGradByGlobalNorm(o["grad_clip_global_norm"]))

    @paddle.jit.to_static
    def step(ids):
        _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    batches = [_ids(m, seed=s) for s in (4, 5)]
    losses = [float(step(paddle.to_tensor(b)).item()) for b in batches]
    norms = lambda g: jnp.stack([jnp.sqrt(jnp.sum(a * a)) for a in g.values()])
    ref_losses, _, w2 = R.train_steps(m, o, w0, batches, norms)
    np.testing.assert_allclose(losses, np.asarray(ref_losses), rtol=1e-5)
    for n, p in model.named_parameters():
        got, want = np.asarray(p.numpy()), np.asarray(w2[n])
        moved = np.abs(want - np.asarray(w0[n])).max()
        assert np.abs(got - want).max() <= 1e-2 * moved, n


def test_expert_counters_are_summed_on_the_device_and_booked_once():
    from paddle_tpu.profiler.metrics import get_registry
    model, m, _ = seeded(tiny_cfg(), 13)
    ids = _ids(m, b=2, s=24, seed=3)
    reg = get_registry()

    def read():
        moe.book_train_stats()
        return [sum(s.value for _, s in reg.counter(n)._iter_series())
                for n in moe.TRAIN_COUNTERS]

    before = read()
    for _ in range(2):
        model(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
    tokens, local, busiest = (a - b for a, b in zip(read(), before))
    passes = 2 * ids.size * m["num_hidden_layers"]
    assert tokens == passes
    # every pair lands on an expert of the router; 8 of 16 are held here
    assert 0 < local < passes * m["num_experts_per_tok"]
    assert local / m["num_experts"] <= busiest <= local
    assert read() == [a + d for a, d in zip(before, (tokens, local, busiest))]
    stats = model.moe_train_stats.numpy()
    assert stats[0] == passes and stats[1] == local


def test_a_dense_mlp_layer_is_refused():
    with pytest.raises(ValueError, match="sparse"):
        MellumForCausalLM(tiny_cfg(mlp_layer_types=("dense",) * 4))


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """HF's ``_compute_yarn_parameters`` transcribed as it reads (float32
    throughout, as torch computes it)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / np.float32(dim))
    extra = np.float32(1.0) / pos_freqs
    inter = np.float32(1.0) / (np.float32(factor) * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    extra_factor = 1 - ramp
    return inter * (1 - extra_factor) + extra * extra_factor


@pytest.mark.parametrize("dim,factor,orig", [(128, 16.0, 8192),
                                             (64, 4.0, 4096),
                                             (16, 4.0, 16)])
def test_yarn_frequencies_match_the_published_formula(dim, factor, orig):
    p = {"rope_type": "yarn", "rope_theta": 500000.0, "factor": factor,
         "original_max_position_embeddings": orig, "beta_fast": 32.0,
         "beta_slow": 1.0}
    inv, att = rope_inv_freq(dim, p)
    want = _hf_yarn(dim, 500000.0, factor, orig, 32.0, 1.0)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert att == pytest.approx(0.1 * math.log(factor) + 1.0)
    # the published attention_factor is the same number
    inv2, att2 = rope_inv_freq(dim, dict(p, attention_factor=1.25))
    assert att2 == 1.25 and np.array_equal(inv, inv2)
    # fast dims keep their frequency, slow ones are divided by the factor
    base = 500000.0 ** (-np.arange(0, dim, 2) / dim)
    assert inv[0] == pytest.approx(base[0])
    assert inv[-1] == pytest.approx(base[-1] / factor, rel=1e-5)
    np.testing.assert_allclose(
        R.yarn_inv_freq(dim, 500000.0, factor, orig, 32.0, 1.0), want,
        rtol=2e-6)


def test_default_rope_and_an_unknown_type():
    inv, att = rope_inv_freq(8, {"rope_theta": 10000.0})
    np.testing.assert_allclose(inv, 10000.0 ** (-np.arange(0, 8, 2) / 8),
                               rtol=1e-6)
    assert att == 1.0
    with pytest.raises(ValueError, match="llama3"):
        rope_inv_freq(8, {"rope_theta": 1.0, "rope_type": "llama3"})
