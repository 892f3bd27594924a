"""Continuous-batching serving engine (SURVEY.md §2.1 inference row):
mixed-length streams through paged KV caches, one compiled decode chunk
for all slots. Oracle: per-stream greedy parity with ``model.generate``
(dense-cache fused decode) on the same prompts."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference import ContinuousBatchingEngine


def _model():
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _ref_greedy(model, prompt, n_new):
    ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
    out, _ = model.generate(ids, max_new_tokens=n_new,
                            decode_strategy="greedy_search",
                            eos_token_id=None, pad_token_id=0)
    return np.asarray(out.numpy())[0].tolist()


@pytest.mark.slow
def test_paged_pool_matches_dense_generate():
    """Single stream sanity: paged prefill + chunked paged decode must
    reproduce the dense-cache greedy tokens exactly."""
    model, cfg = _model()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (11,)).astype(np.int32)
    n_new = 9
    ref = _ref_greedy(model, prompt, n_new)

    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    eng.add_request(prompt, n_new)
    done = eng.run()
    assert len(done) == 1
    assert done[0].tokens == ref, (done[0].tokens, ref)
    assert done[0].finish_reason == "length"


@pytest.mark.slow
def test_mixed_length_streams_more_requests_than_slots():
    """The continuous part: 5 mixed-length requests through 2 slots —
    slots drain and re-admit mid-flight; every stream must match its
    single-stream greedy reference, and page accounting must balance."""
    model, cfg = _model()
    rng = np.random.RandomState(1)
    specs = [(5, 7), (13, 4), (9, 11), (21, 6), (3, 8)]  # (prompt, new)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p, _ in specs]
    refs = [_ref_greedy(model, pr, n) for pr, (_, n) in zip(prompts, specs)]

    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=32, greedy=True)
    ids = [eng.add_request(pr, n) for pr, (_, n) in zip(prompts, specs)]
    free_before = len(eng._free_pages)
    done = eng.run()
    assert sorted(r.request_id for r in done) == sorted(ids)
    by_id = {r.request_id: r for r in done}
    for rid, ref in zip(ids, refs):
        assert by_id[rid].tokens == ref, (rid, by_id[rid].tokens, ref)
    # every page returned to the pool or resident (unreferenced) in
    # the prefix cache — the ISSUE-12 accounting: free + cached is the
    # reusable capacity, and dropping the cache restores the free list
    assert len(eng._free_pages) + eng.prefix_cache_pages == free_before
    eng.reset_prefix_cache()
    assert len(eng._free_pages) == free_before
    assert not eng.active.any()


@pytest.mark.slow
def test_eos_stops_stream_early():
    model, cfg = _model()
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    ref = _ref_greedy(model, prompt, 12)
    # force an early stop partway through the stream. The greedy
    # continuation for this seed repeats its first token for a while, so
    # pick the first DISTINCT token as eos — an eos equal to ref[0]
    # would (correctly) instant-eos at the prefill token instead.
    eos = next(t for t in ref if t != ref[0])
    n_stop = ref.index(eos) + 1
    assert 1 < n_stop < 12      # the scenario is an EARLY mid-stream stop
    # engine-level eos unset: the PER-REQUEST eos alone must stop decode
    eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=8, greedy=True)
    eng.add_request(prompt, 12, eos_token_id=eos)
    (req,) = eng.run()
    assert req.finish_reason == "eos"
    assert req.tokens == ref[:n_stop], (req.tokens, ref)


@pytest.mark.slow
def test_oversized_prompt_uses_exact_bucket():
    """A prompt longer than every configured bucket must still serve —
    through the SAME unified batching-step signature (it streams in
    prefill_chunk-sized slices), never an exact-length recompile."""
    model, cfg = _model()
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    ref = _ref_greedy(model, prompt, 5)
    eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    eng.add_request(prompt, 5)
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)
    # one signature total, even though 20 > every bucket
    assert eng.gauges()["compiled_programs"] == 1, eng._compiled
    assert eng.gauges()["prefill_waves"] == 2     # ceil(20 / 16)


def test_impossible_request_rejected():
    import pytest as _pytest
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                   num_pages=3, max_len=64,
                                   prefill_chunk=8, greedy=True)
    with _pytest.raises(ValueError, match="pages"):
        eng.add_request(np.zeros((20,), np.int32), 10)


@pytest.mark.slow
def test_sampling_mode_deterministic_with_seed():
    """Temperature sampling through the engine: valid tokens, and the
    same seed reproduces the same streams."""
    model, cfg = _model()
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)

    def run(seed):
        eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                       max_len=64, decode_chunk=4,
                                       prefill_chunk=8, greedy=False,
                                       temperature=0.9, seed=seed)
        eng.add_request(prompt, 6)
        (req,) = eng.run()
        return req.tokens

    a, b, c = run(3), run(3), run(4)
    assert a == b, (a, b)
    assert len(a) == 6 and all(0 <= t < cfg.vocab_size for t in a)
    assert a != c  # different seed, different stream (overwhelmingly)


@pytest.mark.slow
def test_qwen2_moe_through_engine():
    """MoE model serving: the paged path threads through Qwen2 too —
    greedy parity vs its dense generate."""
    from paddle_tpu.models import Qwen2MoeConfig, Qwen2MoeForCausalLM
    cfg = Qwen2MoeConfig.tiny()
    cfg.tensor_parallel = False
    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
    ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
    # 6 tokens: step 7 of this seed is a 2.6e-3 argmax near-tie that
    # the paged attention's different reduction order can legitimately
    # flip (MoE routing amplifies ulp-level differences)
    ref_out, _ = model.generate(ids, max_new_tokens=6,
                                decode_strategy="greedy_search",
                                eos_token_id=None, pad_token_id=0)
    ref = np.asarray(ref_out.numpy())[0].tolist()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=48, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    eng.add_request(prompt, 6)
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)


@pytest.mark.slow
def test_gpt2_through_engine():
    """Learned-position model serving: GPT2 (no rope; per-slot position
    embeddings broadcast) — greedy parity vs dense generate."""
    from paddle_tpu.models import GPT2Config, GPT2ForCausalLM
    cfg = GPT2Config.tiny()
    paddle.seed(0)
    model = GPT2ForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
    ref_out, _ = model.generate(ids, max_new_tokens=8,
                                decode_strategy="greedy_search",
                                eos_token_id=None, pad_token_id=0)
    ref = np.asarray(ref_out.numpy())[0].tolist()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=48, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    eng.add_request(prompt, 8)
    (req,) = eng.run()
    assert req.tokens == ref, (req.tokens, ref)

@pytest.mark.slow  # ~4.5s (engine + two compiled programs): fast-gate
def test_one_shot_admitted_mid_stream():
    """Round-5 regression (caught in review): a max_new_tokens=1 request
    admitted WHILE another slot is still decoding must not finish empty
    — its first-token echo rides a speculative chunk that is dispatched
    (clearing the pending flag) before the drain runs; the engine must
    defer draining until that harvest lands. Fast-tier: this is the
    pipelined-branch _admit path the slow one-token test (all requests
    queued before run()) never reaches."""
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=48, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    mid_p = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
    one_p = rng.randint(0, cfg.vocab_size, (5,)).astype(np.int32)
    eng.add_request(long_p, 20)    # keeps slot 0 busy throughout
    eng.add_request(mid_p, 3)      # frees slot 1 mid-stream
    r_one = eng.add_request(one_p, 1)   # admitted into the freed slot
    done = eng.run()
    by_id = {r.request_id: r for r in done}
    assert len(by_id[r_one].tokens) == 1, by_id[r_one].tokens
    assert by_id[r_one].finish_reason == "length"


# ---------------------------------------------------------------------------
# ISSUE 3: chunked/batched prefill, adaptive decode chunks, latency gauges
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chunked_prefill_matches_whole_prompt_prefill():
    """Token parity: streaming a prompt through multiple small prefill
    chunks must be IDENTICAL to a single whole-prompt chunk (both run
    the same paged gather/softmax per query, so the reduction order
    matches exactly), and both must match the dense-cache reference."""
    model, cfg = _model()
    rng = np.random.RandomState(8)
    prompts = [rng.randint(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p in (11, 7, 18)]
    news = [6, 9, 5]
    refs = [_ref_greedy(model, p, n) for p, n in zip(prompts, news)]

    def serve(chunk_len):
        eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                       max_len=64, decode_chunk=4,
                                       prefill_chunk=chunk_len,
                                       greedy=True)
        ids = [eng.add_request(p, n) for p, n in zip(prompts, news)]
        by_id = {r.request_id: r for r in eng.run()}
        return [by_id[i].tokens for i in ids], eng

    whole, eng_w = serve(32)      # every prompt fits one chunk
    chunked, eng_c = serve(4)     # 11 -> 3 waves, 7 -> 2, 18 -> 5
    assert chunked == whole
    assert chunked == refs, (chunked, refs)
    assert eng_c.gauges()["prefill_waves"] > eng_w.gauges()["prefill_waves"]


@pytest.mark.slow
def test_latency_gauges_schema():
    """TTFT / inter-token-latency percentile gauges: present, sane, and
    ordered (p50 <= p99); compiled-program and wave counters exposed."""
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    rng = np.random.RandomState(9)
    for plen, n in [(5, 6), (12, 4), (9, 8)]:
        eng.add_request(rng.randint(0, cfg.vocab_size,
                                    (plen,)).astype(np.int32), n)
    done = eng.run()
    assert len(done) == 3
    g = eng.gauges()
    for k in ("ttft_ms_p50", "ttft_ms_p99", "itl_ms_p50", "itl_ms_p99",
              "compiled_programs", "chunks_empty", "prefill_waves"):
        assert k in g, k
    assert 0 < g["ttft_ms_p50"] <= g["ttft_ms_p99"]
    assert 0 < g["itl_ms_p50"] <= g["itl_ms_p99"]
    assert g["compiled_programs"] == 1          # ONE unified signature
    # 3 prompts through 2 slots: the first TWO admissions share one
    # batched step (the third rides a later one after a drain) — at
    # most one prompt-carrying step per admission is the batching
    assert 1 <= g["prefill_waves"] <= g["prefills"]
    assert g["unified_steps"] == g["chunks_dispatched"] > 0
    # per-request stamps are consistent
    for r in done:
        assert r.t_arrive <= r.t_first <= r.t_done
    # reset clears the latency samples but keeps the compile counter
    eng.reset_gauges()
    g2 = eng.gauges()
    assert g2["ttft_ms_p50"] == 0.0 and g2["itl_ms_p50"] == 0.0
    assert g2["compiled_programs"] == g["compiled_programs"]


@pytest.mark.slow
def test_stall_detection_still_fires():
    """The page-pool-exhaustion stall guard survives ISSUE 10 as the
    true-deadlock diagnostic: a request that can never be admitted
    (pages vanished under the engine, NOTHING occupied to preempt)
    raises instead of spinning. With the accounting audit on, the same
    corruption fails even earlier as the audit AssertionError."""
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=8, greedy=True,
                                   audit=False)
    eng.add_request(np.arange(5, dtype=np.int32), 4)
    eng._free_pages.clear()       # simulate a leaked/fragmented pool
    with pytest.raises(RuntimeError, match="stalled"):
        eng.run()
    # the audited engine reports the same corruption as an accounting
    # failure at the first drain — reclamation bugs cannot hide behind
    # the stall path
    eng2 = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                    max_len=64, decode_chunk=4,
                                    prefill_chunk=8, greedy=True,
                                    audit=True)
    eng2.add_request(np.arange(5, dtype=np.int32), 4)
    eng2._free_pages.clear()
    with pytest.raises(AssertionError, match="page accounting"):
        eng2.run()


def test_compile_budget_mixed_length_workload():
    """Fast-tier CI gate (ISSUE 7 satellite): a mixed-length workload
    through the engine must compile EXACTLY ONE program — the unified
    batching-step signature. Any second signature fails this gate."""
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 1     # smallest servable stack: keep it fast
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=64, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    rng = np.random.RandomState(11)
    # five DISTINCT prompt lengths, two past every bucket — the shapes
    # that exploded the per-bucket signature zoo
    specs = [(5, 8), (9, 8), (13, 8), (17, 8), (21, 8)]
    for plen, n in specs:
        eng.add_request(rng.randint(0, cfg.vocab_size,
                                    (plen,)).astype(np.int32), n)
    done = eng.run()
    assert len(done) == len(specs)
    g = eng.gauges()
    pr3_per_family_baseline = 4   # 1 prefill + pow2 ladder under dc=4
    per_bucket_baseline = 5
    # the hard gate: ONE steady-state compiled batching-step program
    assert g["compiled_programs"] == 1, eng._compiled
    assert g["compiled_programs"] < pr3_per_family_baseline
    assert g["compiled_programs"] < per_bucket_baseline
    (sig,) = eng._compiled
    assert sig[0] == "unified"
    # a second mixed workload on the same engine reuses the signature
    for plen, n in [(7, 3), (19, 2)]:
        eng.add_request(rng.randint(0, cfg.vocab_size,
                                    (plen,)).astype(np.int32), n)
    eng.run()
    assert eng.gauges()["compiled_programs"] == 1, eng._compiled


@pytest.mark.slow
def test_one_token_and_instant_eos_requests():
    """Refactor edge cases: a max_new_tokens=1 request never activates a
    slot (its token arrives via the deferred first-token fetch at
    drain), and a request whose FIRST generated token is its stop token
    is detected on device at the next chunk's entry."""
    model, cfg = _model()
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=48, decode_chunk=4,
                                   prefill_chunk=16, greedy=True)
    rng = np.random.RandomState(0)
    p1 = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    r1 = eng.add_request(p1, 1)                 # one-token request
    # find what the model's first token for p2 would be, then use it as
    # that request's eos -> instant-eos on the prefill token
    p2 = rng.randint(0, cfg.vocab_size, (7,)).astype(np.int32)
    probe = ContinuousBatchingEngine(model, num_slots=1, page_size=8,
                                     max_len=48, decode_chunk=4,
                                     prefill_chunk=16, greedy=True)
    probe.add_request(p2, 2)
    first_tok = probe.run()[0].tokens[0]
    r2 = eng.add_request(p2, 5, eos_token_id=int(first_tok))
    done = eng.run()
    by_id = {r.request_id: r for r in done}
    assert len(by_id[r1].tokens) == 1
    assert by_id[r1].finish_reason == "length"
    assert by_id[r2].tokens[0] == first_tok
    assert len(by_id[r2].tokens) == 1
    assert by_id[r2].finish_reason == "eos"
