"""Nemotron-H (Mamba-2 / LatentMoE / GQA hybrid) against its plain
reference (perfbench/reference/nemotron_h.py) at the tiny preset, seeded
weights: the dense forward, serving through the engine's per-layer cache
spec (chunked prompts, slot reuse, a preemption), the chunked SSD, the
expert shares, and what the engine refuses where a model keeps per-slot
state."""

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
                                             StepCounters, spec_of)
from paddle_tpu.models import (GPT2Config, GPT2ForCausalLM,  # noqa: E402
                               LlamaConfig, LlamaForCausalLM,
                               NemotronHConfig, NemotronHForCausalLM,
                               Qwen2Config, Qwen2ForCausalLM)
from paddle_tpu.ops import mamba2 as ssd  # noqa: E402
from perfbench.harness import weights  # noqa: E402
from perfbench.reference import nemotron_h as R  # noqa: E402

STD = 0.05          # wider than 0.02: at 64 wide the logits would be flat

_SIZE_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "layer_norm_epsilon", "mamba_num_heads",
    "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_topk_prob")


def sizes(cfg):
    """The reference's view of a program config: HF key names, with the
    experts held under ``n_routed_experts`` and the router's width apart."""
    m = {k: getattr(cfg, k) for k in _SIZE_KEYS}
    m["router_num_experts"] = cfg.n_routed_experts
    m["first_held_expert"], m["n_routed_experts"] = cfg.held
    return m


def seeded(cfg, seed):
    """(model, sizes, LeafSource): the program's model and the rule the
    reference makes the same weights from."""
    m = sizes(cfg)
    specs = R.param_specs(m)
    paddle.seed(0)
    model = NemotronHForCausalLM(cfg)
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
    return seeded(NemotronHConfig.tiny(), 11)


# ---- (a) dense forward -------------------------------------------------------

def test_dense_forward_is_the_reference(tiny):
    model, m, src = tiny
    ids = np.random.default_rng(0).integers(0, 128, (3, 21)).astype(np.int32)
    want = R.logits(m, src, jnp.asarray(ids))
    got = model(paddle.to_tensor(ids))._data
    # float32 both sides; the chunked SSD and the sorted expert sums add
    # in another order than the scan and the expert loop
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_parameters_are_built_in_the_config_dtype():
    cfg = NemotronHConfig.tiny()
    cfg.dtype = "bfloat16"
    model = NemotronHForCausalLM(cfg)
    assert {str(p.dtype) for p in model.parameters()} \
        == {"paddle.bfloat16"} or \
        {p._data.dtype for p in model.parameters()} == {jnp.dtype("bfloat16")}
    # the SSM state stays float32 whatever the weights are
    spec = model.cache_spec()
    assert [e.dtype for e in spec if isinstance(e, SlotState)] \
        == ["float32", None] * 2


def test_generate_decodes_what_the_dense_forward_predicts(tiny):
    model, _, _ = tiny
    ids = np.random.default_rng(1).integers(0, 128, (2, 9)).astype(np.int32)
    out, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=4,
                            decode_strategy="greedy_search")
    toks = np.asarray(out._data)
    seq = ids
    for j in range(4):
        lg = np.asarray(model(paddle.to_tensor(seq))._data)[:, -1]
        assert (lg.argmax(-1) == toks[:, j]).all()
        seq = np.concatenate([seq, toks[:, j:j + 1]], 1)


# ---- (b) through the engine --------------------------------------------------

def _serve(eng, reqs):
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.request_id] = r
    return [done[rid] for rid in reqs]


def _worst_gap(m, src, prompt, tokens):
    """Over every served position: reference's best logit minus its logit
    of the token the engine served there (0 = the reference's own
    choice)."""
    ids = np.concatenate([prompt, np.asarray(tokens, np.int32)])[None]
    lg = np.asarray(R.logits(m, src, jnp.asarray(ids)))[0]
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(np.max(rows.max(-1) - rows[np.arange(len(tokens)), tokens]))


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_engine_serves_the_reference_across_slot_reuse(tiny, chunk):
    """Prompts streamed in chunks (shorter than, equal to and longer than
    the SSD's own chunk of 8), then decode, equal the reference's full
    forward at every served position; 7 requests on 3 slots, so slots are
    taken over by new requests and their state has to start from zero."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=64,
                                   page_size=4, prefill_chunk=chunk,
                                   decode_chunk=4, greedy=True, audit=True)
    rng = np.random.default_rng(3)
    shapes = [(5, 6), (19, 9), (8, 3), (27, 12), (3, 7), (12, 5), (16, 4)]
    prompts = [rng.integers(0, 128, L).astype(np.int32) for L, _ in shapes]
    rids = [eng.add_request(p, n) for p, (_, n) in zip(prompts, shapes)]
    for p, (_, n), r in zip(prompts, shapes, _serve(eng, rids)):
        assert r.error is None and len(r.tokens) == n
        # float32 both sides: a served token is the reference's argmax up
        # to summation order
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4
    g = eng.gauges()
    assert g["state_resets"] == len(shapes)
    assert g["compiled_programs"] == 1
    tokens = sum(L + n - 1 for L, n in shapes)
    assert g["moe_tokens"] == 2 * tokens                 # two E layers
    assert g["moe_local_pairs"] == 3 * g["moe_tokens"]   # all experts held
    assert g["state_pool_bytes"] == 3 * 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert g["kv_pool_bytes"] == 2 * 2 * eng.num_pages * 4 * 16 * 4


def test_engine_recomputes_state_after_a_preemption(tiny):
    """A higher-priority arrival evicts a running request; the victim's
    recurrent state is rebuilt by replaying prompt + tokens from position
    0, and its final stream is still the reference's."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=64,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, greedy=True, audit=True)
    rng = np.random.default_rng(5)
    pa, pb, ph = (rng.integers(0, 128, L).astype(np.int32)
                  for L in (6, 9, 7))
    a, b = eng.add_request(pa, 30), eng.add_request(pb, 28)
    for _ in range(3):
        eng.step()
    h = eng.add_request(ph, 12, priority=5)
    ra, rb, rh = _serve(eng, [a, b, h])
    assert ra.preemptions + rb.preemptions >= 1
    g = eng.gauges()
    assert g["preempt_evictions"] >= 1 and g["preempt_recompute_tokens"] >= 1
    assert g["state_resets"] >= 4          # 3 admissions + the replay
    for p, r, n in ((pa, ra, 30), (pb, rb, 28), (ph, rh, 12)):
        assert r.error is None and len(r.tokens) == n
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def _grouped_engine(model, monkeypatch, slots, rows):
    """An engine whose prefill loop runs groups of ``rows`` slots (the
    module constant patched small; chunk 8)."""
    from paddle_tpu.inference import serving
    monkeypatch.setattr(serving, "PREFILL_GROUP_POSITIONS", rows * 8)
    eng = ContinuousBatchingEngine(model, num_slots=slots, max_len=64,
                                   page_size=4, prefill_chunk=8,
                                   decode_chunk=4, greedy=True, audit=True)
    assert eng._group == rows
    return eng


def test_engine_serves_the_reference_in_groups_through_a_preemption(
        tiny, monkeypatch):
    """More prompts than one group of the step's prefill loop holds (four
    slots, groups of three: the state rows are gathered to a group and
    scattered back), slot reuse, and a higher-priority arrival that evicts
    a running request: every stream is the reference's, and every
    admission (the replay too) starts exactly one slot from zero state."""
    model, m, src = tiny
    eng = _grouped_engine(model, monkeypatch, slots=4, rows=3)
    rng = np.random.default_rng(8)
    shapes = [(11, 26), (19, 30), (6, 28), (27, 24), (9, 6), (14, 5)]
    prompts = [rng.integers(0, 128, L).astype(np.int32) for L, _ in shapes]
    rids = [eng.add_request(p, n) for p, (_, n) in zip(prompts, shapes)]
    for _ in range(3):
        eng.step()
    ph = rng.integers(0, 128, 7).astype(np.int32)
    rids.append(eng.add_request(ph, 9, priority=5))
    done = _serve(eng, rids)
    g = eng.gauges()
    assert g["preempt_evictions"] >= 1
    assert g["state_resets"] \
        == len(rids) + sum(r.preemptions for r in done)
    assert g["compiled_programs"] == 1
    for p, (_, n), r in zip(prompts + [ph], shapes + [(7, 9)], done):
        assert r.error is None and len(r.tokens) == n
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def test_a_padding_row_of_a_ragged_group_writes_no_state(tiny, monkeypatch):
    """Four prompts on four slots in groups of three: the last slot is
    row 0 of a ragged last group whose two padding rows GATHER that
    slot's state (their index is clamped for the read) — written back,
    they would put the slot's old state over its new one. Its prompt
    spans three chunks, so each turn's state is the next one's input."""
    model, m, src = tiny
    eng = _grouped_engine(model, monkeypatch, slots=4, rows=3)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 128, L).astype(np.int32)
               for L in (5, 7, 4, 21)]
    rids = [eng.add_request(p, 6) for p in prompts]
    eng.step()
    assert eng.slot_req[3] is not None \
        and eng.slot_req[3].request_id == rids[3]
    state = [np.asarray(eng.pools[i]._data[3]) for i, k in
             enumerate(eng._pool_kinds) if k == "state"]
    assert all(np.abs(a).max() > 0 for a in state)
    for p, r in zip(prompts, _serve(eng, rids)):
        assert r.error is None
        assert _worst_gap(m, src, p, r.tokens) <= 1e-4


def test_held_share_model_serves_its_share_of_the_reference():
    """A model that holds experts 4..7 of 16 serves what the reference,
    given the same share, computes."""
    cfg = NemotronHConfig.tiny()
    cfg.n_routed_experts_held, cfg.first_held_expert = 4, 4
    model, m, src = seeded(cfg, 12)
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


# ---- (c) the chunked SSD -----------------------------------------------------

def _ssd_inputs(seed, B, S, H=8, P=4, G=2, N=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return dict(x=f(B, S, H, P), dt=jax.nn.softplus(f(B, S, H)),
                A=-jnp.exp(f(H)), Bm=f(B, S, G, N), Cm=f(B, S, G, N),
                D=f(H), h0=f(B, H, P, N))


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("carried", [False, True])
def test_chunked_ssd_is_the_step_by_step_recurrence(chunk, carried):
    """From zero and from a carried state, with ragged lengths in one
    batch (dt masked to 0 past a stream's length): same outputs on the
    valid positions, same final state; a stream of length 0 keeps its
    state."""
    a = _ssd_inputs(0, 3, 37)
    lens = jnp.asarray([37, 5, 0])
    live = jnp.arange(37)[None, :] < lens[:, None]
    dt = jnp.where(live[..., None], a["dt"], 0.0)
    h0 = a["h0"] if carried else jnp.zeros_like(a["h0"])
    y1, h1 = ssd.ssd_scan(h0, a["x"], dt, a["A"], a["Bm"], a["Cm"], a["D"])
    y2, h2 = ssd.ssd_chunked(h0, a["x"], dt, a["A"], a["Bm"], a["Cm"],
                             a["D"], chunk=chunk)
    # float32: the chunked form sums a chunk's terms as one matmul
    np.testing.assert_allclose(np.asarray(y2)[np.asarray(live)],
                               np.asarray(y1)[np.asarray(live)], atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2[2]), np.asarray(h0[2]),
                               atol=1e-6)


def test_conv_tail_is_gathered_at_each_streams_own_length():
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, tail, w, b = f(3, 6, 5), f(3, 3, 5), f(4, 5), f(5)
    lens = jnp.asarray([6, 2, 0])
    y, new = ssd.causal_conv_carry(x, tail, w, b, lens)
    seq = np.concatenate([np.asarray(tail), np.asarray(x)], 1)
    for r, n in enumerate([6, 2, 0]):
        np.testing.assert_array_equal(np.asarray(new[r]), seq[r, n:n + 3])
        for t in range(n):
            want = (seq[r, t:t + 4] * np.asarray(w)).sum(0) + np.asarray(b)
            np.testing.assert_allclose(np.asarray(y[r, t]), want, atol=1e-5)


# ---- (d) the shares add up ---------------------------------------------------

def test_four_shares_and_one_shared_expert_are_the_uncut_layer(tiny):
    """The program's expert layer built four times, each holding a quarter
    of the 16 experts (the same weights, sliced), the shared expert counted
    once: their sum is the reference's UNCUT layer."""
    _, m, src = tiny
    norm_w, w = R.layer_weights(src, src.words, 1)
    u = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 64)),
                    jnp.float32)
    want = jax.vmap(lambda s: R.routed_experts(m, w, s, R.mm_f32))(u)
    total = 0
    for q in range(4):
        cfg = NemotronHConfig.tiny()
        cfg.n_routed_experts_held, cfg.first_held_expert = 4, 4 * q
        from paddle_tpu.models.nemotron_h import LatentMoE
        layer = LatentMoE(cfg)
        lo = 4 * q
        for name, p in layer.named_parameters():
            a = w[name]
            p.set_data(a[lo:lo + 4] if name.startswith("experts.") else a)
        out, st = layer(paddle.to_tensor(u), shared=(q == 0))
        total = total + out._data
        assert int(st._data[0]) == 18
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


# ---- (f) the cache spec ------------------------------------------------------

def _uniform(kind):
    paddle.seed(0)
    if kind == "qwen2":
        return Qwen2ForCausalLM(Qwen2Config.tiny())
    if kind == "llama":
        return LlamaForCausalLM(LlamaConfig.tiny())
    return GPT2ForCausalLM(GPT2Config.tiny())


@pytest.mark.parametrize("kind", ["qwen2", "llama", "gpt2"])
@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_uniform_kv_models_keep_their_pools(kind, kv_quant):
    """A model that declares no cache spec gets the pools it had: per
    layer (k, v) of (pages, page, kvh * d) in the model's dtype, and under
    quantized KV (k, v, k_scales, v_scales), scales (pages, kvh, page) —
    shapes, dtypes and order."""
    model = _uniform(kind)
    cfg = model.config
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                   page_size=8, kv_quant=kv_quant)
    kvh = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
    d = getattr(cfg, "head_dim", cfg.hidden_size // cfg.num_attention_heads)
    data, scale = (eng.num_pages, 8, kvh * d), (eng.num_pages, kvh, 8)
    if kv_quant == "none":
        per = [(data, jnp.float32)] * 2
    else:
        per = [(data, jnp.int8)] * 2 + [(scale, jnp.float32)] * 2
    want = per * cfg.num_hidden_layers
    assert [(tuple(p._data.shape), p._data.dtype) for p in eng.pools] \
        == [(s, jnp.dtype(t)) for s, t in want]
    assert spec_of(model) == [PagedKV(kvh, d)] * cfg.num_hidden_layers
    g = eng.gauges()
    assert g["state_pool_bytes"] == 0 and "moe_tokens" not in g
    assert g["kv_pool_bytes"] == g["kv_quant_pool_bytes"] \
        == 2 * cfg.num_hidden_layers * int(np.prod(data)) \
        * (4 if kv_quant == "none" else 1)


def test_the_hybrid_declares_a_cache_per_layer(tiny):
    model, _, _ = tiny
    assert model.cache_spec() == [
        SlotState((8, 8, 16), "float32"), SlotState((3, 128), None),   # M
        PagedKV(2, 16),                                                # *
        SlotState((8, 8, 16), "float32"), SlotState((3, 128), None),   # M
        StepCounters(("moe_tokens", "moe_local_pairs",
                      "moe_max_expert_pairs", "state_resets"))]
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                   page_size=8)
    assert eng._pool_kinds == ["state", "state", "kv", "kv", "state",
                               "state", "counters"]
    assert eng.pools[0]._data.dtype == jnp.float32
    assert tuple(eng.pools[0]._data.shape) == (2, 8, 8, 16)


def test_a_counter_the_model_did_not_declare_is_refused(tiny, monkeypatch):
    """The counters' vocabulary is the model's: its module declares each
    ``serving/<name>``; the engine names none and mints none."""
    model, _, _ = tiny
    spec = model.cache_spec()[:-1] + [StepCounters(("moe_tokens", "made_up"))]
    monkeypatch.setattr(model, "cache_spec", lambda: spec, raising=False)
    with pytest.raises(ValueError, match=r"\['made_up'\] are not declared"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=32, page_size=8)


def test_per_slot_state_switches_the_prefix_cache_off(tiny):
    """A prefix hit would skip tokens whose state nobody stored: the same
    prompt twice is prefilled twice, and served right twice."""
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
def test_per_slot_state_refuses_what_it_cannot_carry(tiny, kw):
    model, _, _ = tiny
    with pytest.raises(ValueError, match="per-slot recurrent state"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                 page_size=8, **kw)


def test_per_slot_state_refuses_an_imported_migration(tiny):
    model, _, _ = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=32,
                                   page_size=8)
    with pytest.raises(ValueError, match="per-slot recurrent state"):
        eng.import_migration(object(), {"version": 1})
