"""Qwen3-Next (gated delta-rule layers beside gated full attention in one
stack, softmax-routed held-share experts beside a gated shared one) against
its plain reference (perfbench/reference/qwen3_next.py) at the tiny preset,
seeded float32 weights: the dense forward, serving through the engine's
per-slot state and pages (prompts split over chunks, then the one-step form;
a slot reused after a finished request; a preemption), the expert shares,
the cache spec, the parameter and cache bytes of the benchmark's
configuration, and what the engine refuses where a model keeps per-slot
state."""

import json
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
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.cache_spec import (PagedKV, SlotState,  # noqa: E402
                                             StepCounters)
from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM  # noqa: E402
from paddle_tpu.models.qwen3_next import COUNTERS, Qwen3NextSparseMoe  # noqa: E402
from perfbench.harness import weights  # noqa: E402
from perfbench.reference import qwen3_next as R  # noqa: E402

STD = 0.05          # wider than 0.02: at 64 wide the logits would be flat

_SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "full_attention_interval", "rms_norm_eps", "num_attention_heads",
    "num_key_value_heads", "head_dim", "partial_rotary_factor", "rope_theta",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim",
    "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob")


def sizes(cfg):
    """The reference's view of a program config: HF key names, with the
    experts held under ``num_experts`` and the router's width apart."""
    m = {k: getattr(cfg, k) for k in _SIZE_KEYS}
    m["router_num_experts"] = cfg.num_experts
    m["first_held_expert"], m["num_experts"] = cfg.held
    return m


def seeded(cfg, seed):
    m = sizes(cfg)
    specs = R.param_specs(m)
    paddle.seed(0)
    model = Qwen3NextForCausalLM(cfg)
    model.eval()
    named = list(model.named_parameters())
    assert [(n, tuple(p.shape)) for n, p in named] \
        == [(n, tuple(s)) for n, s, _ in specs]
    for (_, p), a in zip(named, weights.make_all(specs, seed, STD,
                                                 jnp.float32)):
        p.set_data(a)
    src = weights.LeafSource(specs, weights.seed_words(seed), STD,
                             jnp.float32, R.LAYER_PATTERN)
    return model, m, src


@pytest.fixture(scope="module")
def tiny():
    return seeded(Qwen3NextConfig.tiny(), 21)


def _serve(eng, reqs):
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.request_id] = r
    return [done[rid] for rid in reqs]


def _worst_gap(m, src, prompt, tokens):
    """Over every served position: the reference's best logit minus its
    logit of the token the engine served there (0 = its own choice)."""
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None]
    lg = np.asarray(R.logits(m, src, jnp.asarray(ids)))[0]
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(np.max(rows.max(-1) - rows[np.arange(len(tokens)), tokens]))


# ---- (a) dense forward, generate ----------------------------------------------

def test_the_presets_follow_the_published_pattern():
    assert Qwen3NextConfig.tiny().layer_kinds == (
        "linear_attention",) * 3 + ("full_attention",)
    big = Qwen3NextConfig.qwen3_next_80b_a3b()
    assert big.layer_kinds == (("linear_attention",) * 3
                               + ("full_attention",)) * 12
    assert (big.key_dim, big.value_dim, big.conv_dim, big.rotary_dim) \
        == (2048, 4096, 8192, 64)


def test_dense_forward_is_the_reference(tiny):
    model, m, src = tiny
    # 29 tokens: three whole chunks of 8 and a partial one
    ids = np.random.default_rng(0).integers(0, 128, (3, 29)).astype(np.int32)
    want = R.logits(m, src, jnp.asarray(ids))
    got = model(paddle.to_tensor(ids))._data
    # float32 both sides; the chunked delta rule and the sorted expert sums
    # add in another order than the reference's scan and loop
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_the_reference_attends_in_query_blocks(tiny, monkeypatch):
    """The reference computes attention ``Q_BLOCK`` query rows at a time so
    that the cell's 5,120-token sequences fit: blocks of 8 over 32 tokens
    give what one block gives."""
    _, m, src = tiny
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 128, (2, 32)),
                      jnp.int32)
    want = np.asarray(R.logits(m, src, ids))
    monkeypatch.setattr(R, "Q_BLOCK", 8)
    # the same float32 sums, row by row
    np.testing.assert_allclose(np.asarray(R.logits(m, src, ids)), want,
                               atol=1e-5)


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_moves_the_reference(tiny, fault):
    """The control's faults are real: each moves the logits far past the
    tolerance the program is held to. 140 tokens, so that the state zeroed
    at position 128 has positions after it."""
    _, m, src = tiny
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 140)),
                      jnp.int32)
    off = np.abs(np.asarray(R.logits(dict(m, fault=fault), src, ids))
                 - np.asarray(R.logits(m, src, ids))).max()
    assert off > 1e-3, (fault, off)


def test_parameters_are_built_in_the_config_dtype():
    cfg = Qwen3NextConfig.tiny()
    cfg.dtype = "bfloat16"
    model = Qwen3NextForCausalLM(cfg)
    assert {p._data.dtype for p in model.parameters()} \
        == {jnp.dtype("bfloat16")}


def test_an_mtp_head_is_refused_by_name():
    cfg = Qwen3NextConfig.tiny()
    cfg.mtp_num_hidden_layers = 1
    with pytest.raises(ValueError, match="mtp_num_hidden_layers"):
        Qwen3NextForCausalLM(cfg)


def test_generate_decodes_what_the_dense_forward_predicts(tiny):
    model, _, _ = tiny
    ids = np.random.default_rng(1).integers(0, 128, (2, 13)).astype(np.int32)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                            decode_strategy="greedy_search")
    toks = np.asarray(out._data)
    seq = ids
    for j in range(5):
        lg = np.asarray(model(paddle.to_tensor(seq))._data)[:, -1]
        assert (lg.argmax(-1) == toks[:, j]).all()
        seq = np.concatenate([seq, toks[:, j:j + 1]], 1)


# ---- (b) through the engine: per-slot state beside pages ------------------------

@pytest.mark.parametrize("chunk,page", [(8, 4), (6, 4), (16, 8)])
def test_engine_serves_the_reference_through_state_and_pages(tiny, chunk,
                                                             page):
    """Prompts streamed in chunks (the chunked form, its state carried from
    one engine chunk to the next), then decode (the one-step form), equal
    the reference's full forward at every served position. Requests of
    unequal length; 7 requests on 3 slots, so a slot's state and conv tail
    are taken over by a new request with the old one's still in them (the
    reset at position 0); a chunk of 6 is not a multiple of the delta
    rule's own chunk of 8."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=112,
                                   page_size=page, prefill_chunk=chunk,
                                   decode_chunk=4, greedy=True, audit=True)
    rng = np.random.default_rng(3)
    shapes = [(5, 6), (61, 9), (8, 30), (90, 12), (3, 7), (33, 40), (1, 4)]
    prompts = [rng.integers(0, 128, L).astype(np.int32) for L, _ in shapes]
    rids = [eng.add_request(p, n) for p, (_, n) in zip(prompts, shapes)]
    for p, (_, n), r in zip(prompts, shapes, _serve(eng, rids)):
        assert r.error is None and len(r.tokens) == n
        # float32 both sides: a served token is the reference's argmax up
        # to summation order
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4
    g = eng.gauges()
    assert g["compiled_programs"] == 1
    tokens = sum(L + n - 1 for L, n in shapes)
    prompt_tokens = sum(L for L, _ in shapes)
    assert g["moe_tokens"] == 4 * tokens                 # every layer sparse
    assert g["moe_local_pairs"] == 3 * g["moe_tokens"]   # all experts held
    assert g["gdn_tokens"] == 3 * tokens                 # three L layers
    assert g["gdn_chunk_tokens"] == 3 * prompt_tokens    # prompts: chunked


def test_engine_restores_state_after_a_preemption(tiny):
    """A higher-priority arrival evicts a running request; the victim's
    state is rebuilt by replaying prompt + tokens from position 0 (the
    reset), and its final stream is still the reference's."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=96,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, greedy=True, audit=True)
    rng = np.random.default_rng(5)
    pa, pb, ph = (rng.integers(0, 128, L).astype(np.int32)
                  for L in (26, 19, 7))
    a, b = eng.add_request(pa, 40), eng.add_request(pb, 38)
    for _ in range(6):
        eng.step()
    h = eng.add_request(ph, 12, priority=5)
    ra, rb, rh = _serve(eng, [a, b, h])
    assert ra.preemptions + rb.preemptions >= 1
    for p, r, n in ((pa, ra, 40), (pb, rb, 38), (ph, rh, 12)):
        assert r.error is None and len(r.tokens) == n
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def test_held_share_model_serves_its_share_of_the_reference():
    """A model that holds experts 4..7 of 16 serves what the reference,
    given the same share, computes."""
    cfg = Qwen3NextConfig.tiny()
    cfg.num_experts_held, cfg.first_held_expert = 4, 4
    model, m, src = seeded(cfg, 22)
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=48,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, greedy=True, audit=True)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 128, L).astype(np.int32) for L in (11, 4, 17)]
    rids = [eng.add_request(p, 8) for p in prompts]
    for p, r in zip(prompts, _serve(eng, rids)):
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4
    g = eng.gauges()
    # 3 pairs a token over 16 experts, 4 held: fewer than all, more than none
    assert 0 < g["moe_local_pairs"] < 3 * g["moe_tokens"]


# ---- (c) the shares add up ------------------------------------------------------

def test_eight_shares_and_one_gated_shared_expert_are_the_uncut_layer(tiny):
    """The program's sparse block built eight times, each holding an
    eighth of the 16 experts (the same weights, sliced), the gated shared
    expert counted once: their sum is the reference's UNCUT layer."""
    _, m, src = tiny
    w = R._under(R.layer_weights(src, src.words, 1), "mlp.")
    u = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 64)),
                    jnp.float32)
    want = jax.vmap(lambda s: R.sparse_mlp(m, w, s, R.mm_f32))(u)
    total, pairs = 0, 0
    for q in range(8):
        cfg = Qwen3NextConfig.tiny()
        cfg.num_experts_held, cfg.first_held_expert = 2, 2 * q
        layer = Qwen3NextSparseMoe(cfg)
        for name, p in layer.named_parameters():
            a = w[name]
            p.set_data(a[2 * q:2 * q + 2] if name.startswith("experts.")
                       else a)
        out, st = layer(paddle.to_tensor(u), shared=(q == 0))
        total = total + out._data
        assert int(st._data[0]) == 18
        pairs += int(st._data[1])
    assert pairs == 18 * 3                     # every pair computed once
    # float32: the shares' sums add in another order than the loop
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


# ---- (d) the cache spec, the benchmark's sizes, the refusals --------------------

def test_the_model_declares_a_cache_per_layer_kind(tiny):
    model, _, _ = tiny
    lin = [SlotState((4, 16, 8), "float32"), SlotState((3, 96), None)]
    assert model.cache_spec() == lin * 3 + [PagedKV(2, 16),
                                            StepCounters(COUNTERS)]
    assert COUNTERS == ("moe_tokens", "moe_local_pairs",
                        "moe_max_expert_pairs", "gdn_tokens",
                        "gdn_chunk_tokens")
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=64,
                                   page_size=4, prefill_chunk=8)
    assert eng._pool_kinds == ["state"] * 6 + ["kv"] * 2 + ["counters"]
    assert eng.pools[0]._data.dtype == jnp.float32
    assert eng.gauges()["state_pool_bytes"] \
        == 3 * 3 * (4 * 16 * 8 * 4 + 3 * 96 * 4)


def test_the_benchmark_configuration_counts_what_its_file_reckons():
    """2.93 B parameters, 1.24 GB of per-slot state, 2.01 GB of K/V: the
    numbers of ``memory_reckoning`` from the program's own spec at the
    published widths (shapes only; nothing is allocated)."""
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "qwen3-next-ep8-d12.json")) as f:
        file = json.load(f)
    cfg = Qwen3NextConfig.qwen3_next_80b_a3b()
    for k, v in file["program"]["config"].items():
        setattr(cfg, k, file[v[1:]] if isinstance(v, str) else v)
    model = Qwen3NextForCausalLM(cfg)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == 2_929_374_400
    spec = model.cache_spec()
    state = [e for e in spec if isinstance(e, SlotState)]
    assert state[:2] == [SlotState((32, 128, 128), "float32"),
                         SlotState((3, 8192), None)] and len(state) == 18
    slots = file["engine"]["num_slots"]
    assert slots * sum(int(np.prod(e.shape)) * (4 if e.dtype else 2)
                       for e in state) \
        == 9 * 64 * (2_097_152 + 49_152) == 1_236_271_104
    kv = [e for e in spec if isinstance(e, PagedKV)]
    assert kv == [PagedKV(2, 256)] * 3
    pages = slots * file["engine"]["max_len"] // 16 + 1
    assert 2 * len(kv) * pages * 16 * 512 * 2 == 2_013_364_224


def test_per_slot_state_switches_the_prefix_cache_off(tiny):
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=48,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, prefix_cache=True)
    p = np.random.default_rng(8).integers(0, 128, 17).astype(np.int32)
    (first,) = _serve(eng, [eng.add_request(p, 5)])
    (again,) = _serve(eng, [eng.add_request(p, 5)])
    assert first.tokens == again.tokens
    assert _worst_gap(m, src, p, again.tokens) <= 1e-4
    assert eng.gauges()["prefix_cache_hits"] == 0


@pytest.mark.parametrize("kw", [{"spec_decode": True}, {"spec_k": 2},
                                {"role": "prefill"}])
def test_per_slot_state_refuses_what_it_cannot_carry(tiny, kw):
    model, _, _ = tiny
    with pytest.raises(ValueError, match="per-slot recurrent state"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                 page_size=8, **kw)
