"""Speculative decoding through the ragged kernel (ISSUE 18).

Contracts pinned here:

- greedy spec-on streams are TOKEN-IDENTICAL to the plain unified
  engine for BOTH draft sources (n-gram prompt-lookup and
  self-speculative skip-layer), including eos mid-chunk, K that does
  not divide the generation length, and the acceptance extremes
  (oracle drafts -> accept rate exactly 1.0; adversarial drafts ->
  exactly 0.0 — the rejection resample still emits the right token);
- the host rejection sampler is marginally EXACT: each emitted
  position's empirical distribution matches the target distribution on
  a fixed-seed synthetic logits table;
- spec composes token-identically with the replay paths it must never
  perturb: prefix-cache warm attach (ISSUE 12), priority preemption
  recompute (ISSUE 10), and supervised engine restart (ISSUE 10) —
  draft state is invisible to all three by construction;
- spec economics gauges balance (drafted == accepted + rejected) and
  the ctor resolves K/source through the autotuner ``spec_decode``
  surface when the knobs are left None.

The ``tools/run_gates.py spec_decode`` gate runs this full marker
including slow; the fast tier keeps the host-side units and one small
end-to-end identity.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  EngineSupervisor)
from paddle_tpu.inference.spec_decode import (DraftSource,
                                              NGramDraftSource,
                                              SelfSpecDraftSource,
                                              get_draft_source,
                                              ngram_propose,
                                              rejection_sample)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.spec_decode

_MODEL = None


def _model():
    """One 2-layer tiny model for the whole module. TWO layers on
    purpose: the self-speculative default skips the top half
    (``range((n+1)//2, n)``), which is EMPTY at n=1 — a 1-layer model
    would silently test self-spec with a full-strength draft."""
    global _MODEL
    if _MODEL is None:
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        cfg.num_hidden_layers = 2
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        _MODEL = (m, cfg)
    return _MODEL


def _build(**kw):
    m, _ = _model()
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 48)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("greedy", True)
    return ContinuousBatchingEngine(m, **kw)


def _ref(prompt, n, eos=None):
    """Uncontended single-slot SPEC-OFF stream — the identity oracle."""
    eng = _build(num_slots=1)
    eng.add_request(prompt, n, eos_token_id=eos)
    (req,) = eng.run()
    return req.tokens


def _prompts(seed, shapes):
    _, cfg = _model()
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (p,)).astype(np.int32)
            for p in shapes]


def _assert_balanced(eng):
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1, (
        len(eng._free_pages), eng.prefix_cache_pages, eng.num_pages)
    assert not eng._deferred_free
    assert all(not p for p in eng.slot_pages)
    assert all(not s for s in eng.slot_shared)


class _OracleSource(DraftSource):
    """Proposes each slot's exact reference continuation — every
    dispatched draft must be accepted (the acceptance-K extreme)."""

    name = "oracle"

    def __init__(self, refs):
        self.refs = refs            # request_id -> reference tokens

    def propose(self, eng, slots, k):
        drafts = np.zeros((eng.num_slots, k), np.int32)
        counts = np.zeros((eng.num_slots,), np.int32)
        for slot in slots:
            req = eng.slot_req[slot]
            if req is None or req.request_id not in self.refs:
                continue
            t = len(req.tokens)
            prop = self.refs[req.request_id][t:t + k]
            counts[slot] = len(prop)
            drafts[slot, :len(prop)] = prop
        return drafts, counts


class _AdversarialSource(_OracleSource):
    """Proposes (reference + 1) mod vocab — under greedy every draft
    must be REJECTED, and the rejection resample must still emit the
    correct token (the acceptance-0 extreme)."""

    name = "adversarial"

    def propose(self, eng, slots, k):
        _, cfg = _model()
        drafts, counts = super().propose(eng, slots, k)
        return (drafts + 1) % cfg.vocab_size, counts


# ---------------------------------------------------------------------------
# host-side units: ngram proposal + rejection sampler
# ---------------------------------------------------------------------------


def test_ngram_propose_matches_and_misses():
    # suffix [1,2,3] recurs at the start: propose its continuation
    prop = ngram_propose([1, 2, 3, 9, 4, 1, 2, 3], k=3)
    assert prop.tolist() == [9, 4, 1]
    # all-distinct history: nothing to match at any n
    assert ngram_propose([1, 2, 3, 4, 5], k=4).size == 0
    # proposal is clamped to what actually follows the match
    assert ngram_propose([7, 8, 9, 7, 8, 9], k=8).tolist() == [7, 8, 9]


def test_ngram_propose_longest_n_and_most_recent_win():
    # 3-gram suffix [1,2,3] matches at j=0 (-> 7); the 1-gram [3]
    # ALSO matches later (-> 9) but the longer match must win
    assert ngram_propose([1, 2, 3, 7, 8, 3, 9, 1, 2, 3],
                         k=1).tolist() == [7]
    # same n twice: the MOST RECENT earlier occurrence wins
    assert ngram_propose([1, 2, 5, 1, 2, 6, 1, 2],
                         k=1).tolist() == [6]


def test_rejection_sample_greedy_is_exact_match():
    # p rows put their argmax at 2, 0, 3
    probs = np.eye(4)[[2, 0, 3]] * 0.7 + 0.1
    # drafts match the argmax chain -> all accepted + bonus argmax
    emitted, n_acc = rejection_sample(probs, [2, 0], None, greedy=True)
    assert (emitted, n_acc) == ([2, 0, 3], 2)
    # first draft wrong -> truncate at 0 accepted, emit the argmax
    emitted, n_acc = rejection_sample(probs, [1, 0], None, greedy=True)
    assert (emitted, n_acc) == ([2], 0)
    # second draft wrong -> one accepted, then the position-1 argmax
    emitted, n_acc = rejection_sample(probs, [2, 3], None, greedy=True)
    assert (emitted, n_acc) == ([2, 0], 1)


def test_rejection_sample_marginals_are_exact():
    """The distribution-exactness pin: over many fixed-seed trials the
    empirical marginal at position 0, and at position 1 GIVEN position
    0 accepted, must match the target rows — independent of how bad
    the (fixed) drafts are."""
    rng = np.random.default_rng(1234)
    p0 = np.array([0.5, 0.2, 0.2, 0.1])
    p1 = np.array([0.1, 0.1, 0.2, 0.6])
    p2 = np.array([0.25, 0.25, 0.25, 0.25])
    probs = np.stack([p0, p1, p2])
    drafts = [1, 3]                 # p0[1]=0.2: mostly rejected
    n = 20000
    c0 = np.zeros(4)
    c1 = np.zeros(4)
    for _ in range(n):
        emitted, _ = rejection_sample(probs, drafts, rng)
        c0[emitted[0]] += 1
        if len(emitted) >= 2:
            c1[emitted[1]] += 1
    np.testing.assert_allclose(c0 / n, p0, atol=0.015)
    # position 1 exists iff draft 0 accepted: P = p0[1] = 0.2, and its
    # conditional marginal is exactly p1
    assert abs(c1.sum() / n - 0.2) < 0.015
    np.testing.assert_allclose(c1 / c1.sum(), p1, atol=0.03)


def test_get_draft_source_resolution():
    assert isinstance(get_draft_source("ngram"), NGramDraftSource)
    assert isinstance(get_draft_source("self"), SelfSpecDraftSource)
    assert isinstance(get_draft_source("skip_layer"), SelfSpecDraftSource)
    src = NGramDraftSource(max_n=2)
    assert get_draft_source(src) is src
    with pytest.raises(ValueError):
        get_draft_source("medusa")


def test_ctor_resolves_knobs_through_tuner_surface():
    """spec_k/spec_draft left None resolve through the autotuner's
    ``spec_decode`` surface (override > cache > defaults)."""
    from paddle_tpu import tuner
    assert tuner.get_surface("spec_decode") is not None
    tuner.set_override("spec_decode", {"k": 2, "source": "self"})
    try:
        eng = _build(spec_decode=True)
        assert eng._spec_k == 2
        assert isinstance(eng._spec_source, SelfSpecDraftSource)
    finally:
        tuner.set_override("spec_decode", None)
    # explicit arguments always beat the override
    eng = _build(spec_k=3, spec_draft="ngram")
    assert eng._spec_k == 3
    assert isinstance(eng._spec_source, NGramDraftSource)


# ---------------------------------------------------------------------------
# end-to-end greedy token identity
# ---------------------------------------------------------------------------


def test_greedy_identity_small():
    """Fast-tier smoke: the spec engine with guaranteed drafting
    (oracle source) matches the plain stream exactly, with real
    acceptances flowing into the economics gauges."""
    (prompt,) = _prompts(3, (7,))
    ref = _ref(prompt, 10)
    eng = _build(num_slots=1, spec_k=4, spec_draft="ngram")
    rid = eng.add_request(prompt, 10)
    eng._spec_source = _OracleSource({rid: ref})
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)
    g = eng.gauges()
    assert g["spec_steps"] >= 1
    assert g["spec_tokens_drafted"] >= 1
    assert g["spec_tokens_drafted"] == (g["spec_tokens_accepted"]
                                        + g["spec_tokens_rejected"])
    _assert_balanced(eng)


@pytest.mark.slow
@pytest.mark.parametrize("source", ["ngram", "self"])
def test_greedy_identity_mixed_batch(source):
    """THE identity pin, both draft sources: a mixed-length batch with
    more requests than slots (drain + re-admit mid-flight) produces
    bitwise the plain engine's streams."""
    specs = [(6, 12), (13, 8), (9, 14)]
    prompts = _prompts(11, [p for p, _ in specs])
    refs = [_ref(p, n) for p, (_, n) in zip(prompts, specs)]
    eng = _build(spec_k=4, spec_draft=source)
    ids = [eng.add_request(p, n) for p, (_, n) in zip(prompts, specs)]
    by = {r.request_id: r for r in eng.run()}
    for rid, ref in zip(ids, refs):
        assert by[rid].tokens == ref, (source, by[rid].tokens, ref)
    assert all(by[i].finish_reason == "length" for i in ids)
    g = eng.gauges()
    assert g["spec_steps"] >= 1
    assert g["spec_tokens_drafted"] == (g["spec_tokens_accepted"]
                                        + g["spec_tokens_rejected"])
    assert 0.0 <= g["spec_accept_rate"] <= 1.0
    _assert_balanced(eng)


@pytest.mark.slow
@pytest.mark.parametrize("source", ["ngram", "self", "oracle"])
def test_eos_mid_chunk_identical(source):
    """A per-request eos that lands MID verification chunk must stop
    the stream at exactly the plain engine's position — the eos token
    emits, nothing after it. The oracle variant FORCES multi-token
    chunks that straddle the eos position (the others cover the real
    sources, whatever their acceptance luck)."""
    (prompt,) = _prompts(2, (6,))
    full = _ref(prompt, 12)
    eos = next(t for t in full if t != full[0])
    n_stop = full.index(eos) + 1
    assert 1 < n_stop < 12          # genuinely mid-stream
    ref = _ref(prompt, 12, eos=eos)
    assert ref == full[:n_stop]
    eng = _build(num_slots=1, spec_k=4,
                 spec_draft="ngram" if source == "oracle" else source)
    rid = eng.add_request(prompt, 12, eos_token_id=eos)
    if source == "oracle":
        # drafts follow the NO-eos continuation: the chunk rides past
        # the eos position and the in-program mask must trim it
        eng._spec_source = _OracleSource({rid: full})
    (req,) = eng.run()
    assert req.finish_reason == "eos"
    assert req.tokens == ref, (source, req.tokens, ref)
    if source == "oracle":
        assert eng.gauges()["spec_tokens_drafted"] >= 1
    _assert_balanced(eng)


@pytest.mark.slow
def test_k_does_not_divide_generation_length():
    """K=5, n_new=14, all-accepted drafts: chunks emit 6 + 6 + 2 — the
    final chunk's draft count is clamped by the remaining budget and
    the stream still matches exactly."""
    (prompt,) = _prompts(5, (9,))
    ref = _ref(prompt, 14)
    eng = _build(num_slots=1, spec_k=5, spec_draft="ngram")
    rid = eng.add_request(prompt, 14)
    eng._spec_source = _OracleSource({rid: ref})
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)
    g = eng.gauges()
    assert g["spec_accept_rate"] == 1.0, g
    assert g["spec_tokens_drafted"] >= 6    # 5 + clamped tail
    _assert_balanced(eng)


@pytest.mark.slow
def test_acceptance_extremes():
    """Oracle drafts: accept rate EXACTLY 1.0. Adversarial drafts:
    EXACTLY 0.0 — and both streams stay token-identical (rejection
    resample == the plain greedy token)."""
    (prompt,) = _prompts(13, (7,))
    ref = _ref(prompt, 13)

    eng = _build(num_slots=1, spec_k=4, spec_draft="ngram")
    rid = eng.add_request(prompt, 13)
    eng._spec_source = _OracleSource({rid: ref})
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)
    g = eng.gauges()
    assert g["spec_tokens_drafted"] >= 4
    assert g["spec_accept_rate"] == 1.0, g
    assert g["spec_tokens_rejected"] == 0
    _assert_balanced(eng)

    eng = _build(num_slots=1, spec_k=4, spec_draft="ngram")
    rid = eng.add_request(prompt, 13)
    eng._spec_source = _AdversarialSource({rid: ref})
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)
    g = eng.gauges()
    assert g["spec_tokens_drafted"] >= 4
    assert g["spec_accept_rate"] == 0.0, g
    assert g["spec_tokens_accepted"] == 0
    _assert_balanced(eng)


@pytest.mark.slow
def test_sampling_mode_completes():
    """greedy=False exercises the in-program rejection sampler
    (accept-u < p, residual resample, bonus): streams complete at the
    requested lengths with balanced pages. (Marginal exactness of the
    rule itself is pinned host-side above — same math, same layout.)"""
    prompts = _prompts(17, (6, 9))
    eng = _build(greedy=False, spec_k=4, spec_draft="ngram")
    ids = [eng.add_request(p, n) for p, n in zip(prompts, (8, 6))]
    by = {r.request_id: r for r in eng.run()}
    assert sorted(by) == sorted(ids)
    assert [len(by[i].tokens) for i in ids] == [8, 6]
    _assert_balanced(eng)


# ---------------------------------------------------------------------------
# composition pins: the replay paths must not see draft state
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_spec_with_prefix_cache_warm_attach_identical():
    """Spec x prefix cache (ISSUE 12): a warm second run attaches
    cached prompt pages (only COMMITTED prompt KV is ever published)
    and the spec stream still equals the cache-off plain reference."""
    _, cfg = _model()
    rng = np.random.RandomState(19)
    prompt = np.tile(rng.randint(0, cfg.vocab_size,
                                 (4,)).astype(np.int32), 4)  # 16 = 2 pages
    ref = _ref(prompt, 8)           # spec-off, cache irrelevant (cold)
    eng = _build(num_slots=1, spec_k=4, spec_draft="ngram")
    for _ in range(2):              # second run sees a warm cache
        eng.add_request(prompt, 8)
        (req,) = eng.run()
        assert req.tokens == ref, (req.tokens, ref)
    g = eng.gauges()
    assert g["prefix_cache_hits"] >= 1
    assert g["prefix_cache_tokens_saved"] >= 8
    _assert_balanced(eng)


@pytest.mark.slow
def test_spec_with_priority_preemption_identical():
    """Spec x preemption (ISSUE 10): a higher-priority arrival evicts a
    speculating victim; its recompute-style replay reconstructs from
    prompt + emitted tokens only — the final streams must equal the
    uncontended spec-off references."""
    pA, pB, pH = _prompts(7, (6, 9, 7))
    refA, refB, refH = _ref(pA, 30), _ref(pB, 28), _ref(pH, 20)
    eng = _build(spec_k=4, spec_draft="ngram")
    a = eng.add_request(pA, 30)
    b = eng.add_request(pB, 28)
    for _ in range(3):
        eng.step()                  # both slots decoding (drafting)
    h = eng.add_request(pH, 20, priority=5)   # pool can't serve all 3
    done = eng.run()
    by = {r.request_id: r for r in done}
    assert sorted(by) == sorted([a, b, h])
    assert all(r.error is None for r in done)
    assert by[h].tokens == refH
    assert by[a].tokens == refA, (by[a].tokens, refA)
    assert by[b].tokens == refB, (by[b].tokens, refB)
    assert by[a].preemptions + by[b].preemptions >= 1
    assert eng.gauges()["preempt_evictions"] >= 1
    _assert_balanced(eng)


@pytest.mark.slow
def test_spec_with_supervisor_restart_identical():
    """Spec x supervised restart (ISSUE 10/11): the engine dies
    mid-stream, the supervisor rebuilds a SPEC engine and replays from
    prompt + emitted tokens — delivered prefixes are never re-served
    and the final stream equals the spec-off reference."""
    (pA,) = _prompts(43, (6,))
    refA = _ref(pA, 8)
    calls = {"n": 0}

    def factory():
        eng = _build(max_containments=0, spec_k=4, spec_draft="ngram")
        orig = eng._harvest_step

        def dying(rec):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("injected engine death")
            return orig(rec)

        eng._harvest_step = dying
        return eng

    sup = EngineSupervisor(factory, max_restarts=3)
    rid = sup.add_request(pA, 8)
    done = sup.run()
    assert sup.restarts >= 1
    by = {r.request_id: r for r in done}
    assert by[rid].tokens == refA, (by[rid].tokens, refA)
    _assert_balanced(sup.engine)


@pytest.mark.slow
def test_gauges_reset_and_rebalance():
    """reset_gauges zeroes the spec economics counters so bench warmup
    compiles never pollute the measured accept rate."""
    _, cfg = _model()
    prompt = np.tile(np.arange(4, dtype=np.int32) % cfg.vocab_size, 3)
    eng = _build(num_slots=1, spec_k=4, spec_draft="ngram")
    eng.add_request(prompt, 6)
    eng.run()
    assert eng.gauges()["spec_steps"] >= 1
    eng.reset_gauges()
    g = eng.gauges()
    assert g["spec_steps"] == 0
    assert g["spec_tokens_drafted"] == 0
    assert g["spec_accept_rate"] == 0.0
