"""EXAONE-MoE (window layers beside global ones, QK-norm, gated held-share
experts beside a shared one) against its plain reference
(perfbench/reference/exaone_moe.py) at the tiny preset, seeded float32
weights: the dense forward, serving through the engine's window rings and
global pages (contexts that pass the window many times, ring wrap-around at
pages that are not chunk-aligned, a preemption), the expert shares, the
cache spec and what the engine refuses where a model keeps window rings."""

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
from paddle_tpu.inference.cache_spec import (PagedKV, StepCounters,  # noqa: E402
                                             WindowKV, ring_pages)
from paddle_tpu.models import ExaoneMoeConfig, ExaoneMoeForCausalLM  # noqa: E402
from paddle_tpu.models.exaone_moe import COUNTERS, ExaoneSparseMoe  # noqa: E402
from perfbench.harness import weights  # noqa: E402
from perfbench.reference import exaone_moe as R  # noqa: E402

STD = 0.05          # wider than 0.02: at 64 wide the logits would be flat

_SIZE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "sliding_window", "rms_norm_eps", "num_experts_per_tok",
    "moe_intermediate_size", "num_shared_experts", "routed_scaling_factor",
    "norm_topk_prob")


def sizes(cfg):
    """The reference's view of a program config: HF key names, with the
    experts held under ``num_experts`` and the router's width apart."""
    m = {k: getattr(cfg, k) for k in _SIZE_KEYS}
    m["layer_types"] = list(cfg.attention_kinds)
    m["mlp_layer_types"] = list(cfg.mlp_kinds)
    m["rope_parameters"] = {"rope_theta": cfg.rope_theta}
    m["router_num_experts"] = cfg.num_experts
    m["first_held_expert"], m["num_experts"] = cfg.held
    return m


def seeded(cfg, seed):
    m = sizes(cfg)
    specs = R.param_specs(m)
    paddle.seed(0)
    model = ExaoneMoeForCausalLM(cfg)
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
    return seeded(ExaoneMoeConfig.tiny(), 21)


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

def test_the_tiny_preset_has_every_kind_of_layer():
    cfg = ExaoneMoeConfig.tiny()
    assert cfg.attention_kinds == ("sliding_attention",) * 3 \
        + ("full_attention", "sliding_attention")
    assert cfg.mlp_kinds == ("dense",) + ("sparse",) * 4


def test_dense_forward_is_the_reference(tiny):
    model, m, src = tiny
    # 29 tokens: the window of 8 is passed three times
    ids = np.random.default_rng(0).integers(0, 128, (3, 29)).astype(np.int32)
    want = R.logits(m, src, jnp.asarray(ids))
    got = model(paddle.to_tensor(ids))._data
    # float32 both sides; the sorted expert sums add in another order than
    # the reference's loop over experts
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("fault", R.FAULTS)
def test_a_planted_fault_moves_the_reference(tiny, fault):
    """The control's faults are real: each moves the logits far past the
    tolerance the program is held to (so the tests above would see a model
    without the window mask, with rotary on the global layer, or short of
    an expert)."""
    _, m, src = tiny
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (1, 29)),
                      jnp.int32)
    off = np.abs(np.asarray(R.logits(dict(m, fault=fault), src, ids))
                 - np.asarray(R.logits(m, src, ids))).max()
    assert off > 1e-3, (fault, off)


def test_parameters_are_built_in_the_config_dtype():
    cfg = ExaoneMoeConfig.tiny()
    cfg.dtype = "bfloat16"
    model = ExaoneMoeForCausalLM(cfg)
    assert {p._data.dtype for p in model.parameters()} \
        == {jnp.dtype("bfloat16")}


def test_a_drafter_is_refused_by_name():
    cfg = ExaoneMoeConfig.tiny()
    cfg.num_nextn_predict_layers = 1
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        ExaoneMoeForCausalLM(cfg)


def test_generate_decodes_what_the_dense_forward_predicts(tiny):
    model, _, _ = tiny
    # prompt 13 + 5 new: decode positions lie past the window of 8
    ids = np.random.default_rng(1).integers(0, 128, (2, 13)).astype(np.int32)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=5,
                            decode_strategy="greedy_search")
    toks = np.asarray(out._data)
    seq = ids
    for j in range(5):
        lg = np.asarray(model(paddle.to_tensor(seq))._data)[:, -1]
        assert (lg.argmax(-1) == toks[:, j]).all()
        seq = np.concatenate([seq, toks[:, j:j + 1]], 1)


# ---- (b) through the engine: rings beside pages --------------------------------

@pytest.mark.parametrize("chunk,page", [(8, 4), (6, 4), (16, 8), (5, 16)])
def test_engine_serves_the_reference_through_rings_and_pages(tiny, chunk,
                                                             page):
    """Prompts streamed in chunks, then decode, equal the reference's full
    forward at every served position. Contexts reach 12 x the window of 8,
    so every ring wraps many times; chunks of 6 and 5 tokens put the wrap
    at pages that are not chunk-aligned; 7 requests on 3 slots, so a ring
    is taken over by a new request with the old one's K/V still in it."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=112,
                                   page_size=page, prefill_chunk=chunk,
                                   decode_chunk=4, greedy=True, audit=True)
    assert eng._ring == ring_pages(8, chunk, page) < eng.pages_per_slot
    rng = np.random.default_rng(3)
    shapes = [(5, 6), (61, 9), (8, 30), (90, 12), (3, 7), (33, 40), (16, 4)]
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
    assert g["moe_tokens"] == 4 * tokens                 # four sparse layers
    assert g["moe_local_pairs"] == 3 * g["moe_tokens"]   # all experts held


def test_engine_refills_a_ring_after_a_preemption(tiny):
    """A higher-priority arrival evicts a running request whose context is
    past the window; the victim's rings are refilled by replaying prompt +
    tokens from position 0, and its final stream is still the
    reference's."""
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
    g = eng.gauges()
    assert g["preempt_evictions"] >= 1 and g["preempt_recompute_tokens"] >= 1
    for p, r, n in ((pa, ra, 40), (pb, rb, 38), (ph, rh, 12)):
        assert r.error is None and len(r.tokens) == n
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def test_a_ragged_group_of_the_prefill_loop_keeps_its_rings_apart(
        tiny, monkeypatch):
    """Four prompts on four slots in groups of three: the ring table's
    rows are gathered to a group like the global table's, and the two
    padding rows of the last group write nowhere."""
    from paddle_tpu.inference import serving
    model, m, src = tiny
    monkeypatch.setattr(serving, "PREFILL_GROUP_POSITIONS", 3 * 8)
    eng = ContinuousBatchingEngine(model, num_slots=4, max_len=64,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, greedy=True, audit=True)
    assert eng._group == 3
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 128, L).astype(np.int32)
               for L in (5, 27, 14, 41)]
    rids = [eng.add_request(p, 9) for p in prompts]
    for p, r in zip(prompts, _serve(eng, rids)):
        assert r.error is None
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def test_held_share_model_serves_its_share_of_the_reference():
    """A model that holds experts 4..7 of 16 serves what the reference,
    given the same share, computes."""
    cfg = ExaoneMoeConfig.tiny()
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

def test_eight_shares_and_one_shared_expert_are_the_uncut_layer(tiny):
    """The program's sparse block built eight times, each holding an
    eighth of the 16 experts (the same weights, sliced), the shared expert
    counted once: their sum is the reference's UNCUT layer."""
    _, m, src = tiny
    w = R._under(R.layer_weights(src, src.words, 1), "mlp.")
    u = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 64)),
                    jnp.float32)
    want = jax.vmap(lambda s: R.sparse_mlp(m, w, s, R.mm_f32))(u)
    total, pairs = 0, 0
    for q in range(8):
        cfg = ExaoneMoeConfig.tiny()
        cfg.num_experts_held, cfg.first_held_expert = 2, 2 * q
        layer = ExaoneSparseMoe(cfg)
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


# ---- (d) the cache spec, and what a ring cannot do -----------------------------

def test_the_model_declares_a_cache_per_layer_kind(tiny):
    model, _, _ = tiny
    assert model.cache_spec() == [
        WindowKV(2, 16, 8), WindowKV(2, 16, 8), WindowKV(2, 16, 8),
        PagedKV(2, 16), WindowKV(2, 16, 8), StepCounters(COUNTERS)]
    assert COUNTERS == ("moe_tokens", "moe_local_pairs",
                        "moe_max_expert_pairs")
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=64,
                                   page_size=4, prefill_chunk=8)
    assert eng._pool_kinds == ["wkv"] * 6 + ["kv"] * 2 + ["wkv"] * 2 \
        + ["counters"]
    R_ = ring_pages(8, 8, 4)
    assert R_ == 5 and eng._ring == R_
    assert tuple(eng.pools[0]._data.shape) == (3 * R_ + 1, 4, 32)
    assert tuple(eng.pools[6]._data.shape) == (3 * 16 + 1, 4, 32)
    # the host allocator's pages are the global pools' alone
    assert len(eng._free_pages) == eng.num_pages - 1 == 3 * 16


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_window_pools_do_not_grow_with_max_len(tiny, kv_quant):
    model, _, _ = tiny
    by_len = {}
    for max_len in (64, 256, 1024):
        eng = ContinuousBatchingEngine(model, num_slots=3, max_len=max_len,
                                       page_size=4, prefill_chunk=8,
                                       kv_quant=kv_quant)
        g = eng.gauges()
        by_len[max_len] = (g["window_pool_bytes"], g["kv_pool_bytes"])
    assert by_len[64][0] == by_len[256][0] == by_len[1024][0] > 0
    assert by_len[64][1] < by_len[256][1] < by_len[1024][1]
    item = 4 if kv_quant == "none" else 1
    scales = 0 if kv_quant == "none" else 8 * (3 * 5 + 1) * 2 * 4 * 4
    assert by_len[64][0] == 8 * (3 * 5 + 1) * 4 * 32 * item + scales


def test_a_ring_is_never_longer_than_a_slots_table_row(tiny):
    model, _, _ = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=12,
                                   page_size=4, prefill_chunk=8)
    assert eng._ring == eng.pages_per_slot == 3


def test_window_rings_switch_the_prefix_cache_off(tiny):
    """A prefix hit would skip tokens whose window K/V nobody stored: the
    same prompt twice is prefilled twice, and served right twice."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=48,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, prefix_cache=True)
    p = np.random.default_rng(8).integers(0, 128, 17).astype(np.int32)
    (first,) = _serve(eng, [eng.add_request(p, 5)])
    (again,) = _serve(eng, [eng.add_request(p, 5)])
    assert first.tokens == again.tokens
    assert _worst_gap(m, src, p, again.tokens) <= 1e-4
    g = eng.gauges()
    assert g["prefix_cache_hits"] == 0 and g["prefix_cache_pages"] == 0


@pytest.mark.parametrize("kw", [{"spec_decode": True}, {"spec_k": 2},
                                {"role": "prefill"}])
def test_window_rings_refuse_what_they_cannot_carry(tiny, kw):
    model, _, _ = tiny
    with pytest.raises(ValueError, match="per-slot window rings"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                 page_size=8, **kw)


def test_window_rings_refuse_an_imported_migration(tiny):
    model, _, _ = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                   page_size=8)
    with pytest.raises(ValueError, match="per-slot window rings"):
        eng.import_migration(object(), {"version": 1})
