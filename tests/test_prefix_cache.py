"""Radix-tree prefix caching with copy-on-write page sharing in the
paged KV pool (ISSUE 12).

Contract pinned here:

- greedy token streams are IDENTICAL cache-on vs cache-off (sharing is
  numerics-transparent — attached pages hold exactly the KV the
  request would have computed);
- a fully-cached prompt COW-forks its last shared page (the final
  token must re-prefill for logits) instead of re-prefilling the page;
- a prompt diverging MID-PAGE shares only the full pages before the
  divergence (block hashing is page-granular);
- cancelling or preempting a shared-page owner decrements refcounts
  without double-freeing (the sharer keeps reading; the owner's replay
  is token-identical);
- eviction is refcount-aware LRU: unreferenced cache pages are
  reclaimed under allocation pressure, referenced ones never;
- the extended ``PADDLE_TPU_SERVING_AUDIT`` invariant (suite-wide on)
  holds: free + private + cache + deferred + trash == num_pages with
  exact refcounts — and a corrupted refcount FAILS it;
- the fleet router's prefix-affinity hint routes same-prefix requests
  to the replica that served the prefix last, below health and
  least-loaded, never to an ejected replica.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  RequestCancelled, ServingFleet)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

_MODEL = None


def _model():
    global _MODEL
    if _MODEL is None:
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        cfg.num_hidden_layers = 1
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        _MODEL = (m, cfg)
    return _MODEL


def _engine(**kw):
    m, _ = _model()
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 32)
    kw.setdefault("greedy", True)
    return ContinuousBatchingEngine(m, **kw)


def _ref_off(specs, **kw):
    """Cache-OFF greedy streams for (prompt, n_new) specs — the
    transparency oracle."""
    eng = _engine(prefix_cache=False, **kw)
    ids = [eng.add_request(p, n) for p, n in specs]
    by = {r.request_id: r for r in eng.run()}
    return [by[i].tokens for i in ids]


def _balanced(eng):
    assert len(eng._free_pages) + eng.prefix_cache_pages \
        == eng.num_pages - 1, (
        len(eng._free_pages), eng.prefix_cache_pages, eng.num_pages)
    assert not eng._deferred_free
    assert all(not p for p in eng.slot_pages)
    assert all(not s for s in eng.slot_shared)
    eng._audit_pages("test")


def test_cache_on_off_token_identical():
    """THE transparency pin: a shared-prefix batch produces bitwise
    the same greedy streams with the cache on and off — and the warm
    run actually shares (hits, tokens saved)."""
    _, cfg = _model()
    rng = np.random.RandomState(7)
    shared = rng.randint(0, cfg.vocab_size, (19,)).astype(np.int32)
    specs = []
    for i in range(6):
        tail = rng.randint(0, cfg.vocab_size,
                           (int(rng.randint(0, 6)),)).astype(np.int32)
        specs.append((np.concatenate([shared, tail]),
                      int(rng.randint(3, 7))))
    refs = _ref_off(specs)

    eng = _engine()
    ids = [eng.add_request(p, n) for p, n in specs]
    by = {r.request_id: r for r in eng.run()}
    for rid, ref in zip(ids, refs):
        assert by[rid].tokens == ref, (rid, by[rid].tokens, ref)
    g = eng.gauges()
    assert g["prefix_cache_hits"] >= 1
    # 19-token shared prefix = 2 full pages -> >= 16 tokens skipped
    # per hit
    assert g["prefix_cache_tokens_saved"] >= 16
    assert g["prefix_cache_pages"] >= 2
    _balanced(eng)


def test_cow_fork_on_fully_cached_prompt():
    """A prompt that is ENTIRELY resident (exact page multiple) must
    fork its last shared page copy-on-write — re-prefilling only the
    final token — and still match the cache-off stream exactly."""
    _, cfg = _model()
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    specs = [(prompt, 5), (prompt, 5)]
    refs = _ref_off(specs)

    eng = _engine()
    ids, by = [], {}
    for p, n in specs:          # sequential: the second admission
        ids.append(eng.add_request(p, n))    # sees a warm cache
        by.update({r.request_id: r for r in eng.run()})
    for rid, ref in zip(ids, refs):
        assert by[rid].tokens == ref
    g = eng.gauges()
    assert g["prefix_cache_cow_forks"] >= 1
    # the COW hit skipped all but ONE prompt token
    assert g["prefix_cache_tokens_saved"] >= 15
    _balanced(eng)


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_cow_copies_one_page_of_every_pool(kv_quant):
    """The fork indexes the pools' page axis (0): after it ``dst`` holds
    ``src``'s content in every K/V pool — and scales pool — and no other
    page moved."""
    _, cfg = _model()
    eng = _engine(kv_quant=kv_quant)
    eng.add_request(np.arange(1, 20, dtype=np.int32) % cfg.vocab_size, 3)
    eng.run()
    before = [np.asarray(a) for a in eng._paged_arrays()]
    src = next(p for p in range(1, eng.num_pages) if before[0][p].any())
    dst = next(p for p in range(1, eng.num_pages)
               if p != src and not before[0][p].any())
    eng._pc_cow(src, dst)
    after = [np.asarray(a) for a in eng._paged_arrays()]
    assert len(after) == (2 if kv_quant == "none" else 4) \
        * cfg.num_hidden_layers
    for b, a in zip(before, after):
        assert a.shape == b.shape and a.shape[0] == eng.num_pages
        np.testing.assert_array_equal(a[dst], b[src])
        keep = [p for p in range(eng.num_pages) if p != dst]
        np.testing.assert_array_equal(a[keep], b[keep])


def test_divergence_mid_page_shares_only_full_blocks():
    """B shares A's first page then diverges INSIDE the second page:
    only the full matching block is shared (page-granular hashing),
    the diverging page is recomputed privately, and the stream still
    matches cache-off."""
    _, cfg = _model()
    rng = np.random.RandomState(13)
    a = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    b = a.copy()
    b[11] = (b[11] + 1) % cfg.vocab_size      # mid-page-2 divergence
    specs = [(a, 4), (b, 4)]
    refs = _ref_off(specs)

    eng = _engine()
    ids, by = [], {}
    for p, n in specs:          # sequential: B sees A's published pages
        ids.append(eng.add_request(p, n))
        by.update({r.request_id: r for r in eng.run()})
    for rid, ref in zip(ids, refs):
        assert by[rid].tokens == ref
    g = eng.gauges()
    assert g["prefix_cache_hits"] == 1         # B hit A's first page
    assert g["prefix_cache_tokens_saved"] == 8  # exactly one block
    assert g["prefix_cache_cow_forks"] == 0
    _balanced(eng)


def test_cancel_shared_page_owner_no_double_free():
    """Cancel the request that PUBLISHED the shared prefix while a
    sharer is still reading it: the owner's detach only decrements
    refcounts — the sharer finishes token-identical, nothing
    double-frees, the audit stays green."""
    _, cfg = _model()
    rng = np.random.RandomState(17)
    shared = rng.randint(0, cfg.vocab_size, (17,)).astype(np.int32)
    tail = rng.randint(0, cfg.vocab_size, (3,)).astype(np.int32)
    pb = np.concatenate([shared, tail])
    ref_b = _ref_off([(pb, 6)])[0]

    eng = _engine()
    rid_a = eng.add_request(shared, 24)       # long-running owner
    for _ in range(2):
        eng.step()                            # A admitted + published
    assert eng.prefix_cache_pages >= 2
    rid_b = eng.add_request(pb, 6)
    eng.step()                                # B attached to A's pages
    assert any(eng.slot_shared), "sharer did not attach"
    assert eng.cancel(rid_a)
    done = []
    for _ in range(200):
        done.extend(eng.step())
        if not eng.has_work():
            break
    by = {r.request_id: r for r in done}
    assert isinstance(by[rid_a].error, RequestCancelled)
    assert by[rid_b].error is None
    assert by[rid_b].tokens == ref_b, (by[rid_b].tokens, ref_b)
    _balanced(eng)


def test_preempt_shared_page_owner_replay_token_identical():
    """A higher-priority latecomer preempts the shared-prefix OWNER
    mid-decode: refcounts drop without freeing the shared pages (the
    sharer keeps reading), and the owner's recompute replay — which
    itself re-hits the cache — is token-identical."""
    _, cfg = _model()
    rng = np.random.RandomState(19)
    shared = rng.randint(0, cfg.vocab_size, (17,)).astype(np.int32)
    pb = np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size, (2,)).astype(np.int32)])
    pc = rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32)
    refs = _ref_off([(shared, 24), (pb, 20), (pc, 5)])

    eng = _engine()
    rid_a = eng.add_request(shared, 24, priority=0)   # the owner
    rid_b = eng.add_request(pb, 20, priority=1)       # the sharer
    for _ in range(2):
        eng.step()                # both mid-decode, slots full
    rid_c = eng.add_request(pc, 5, priority=2)        # the preemptor
    done = eng.run()
    by = {r.request_id: r for r in done}
    assert eng._stats["preempt_evictions"] >= 1
    assert by[rid_a].preemptions >= 1
    for rid, ref in zip((rid_a, rid_b, rid_c), refs):
        assert by[rid].error is None
        assert by[rid].tokens == ref, (rid, by[rid].tokens, ref)
    _balanced(eng)


def test_eviction_is_refcount_aware_lru():
    """A pool too small for every finished prompt's pages to stay
    resident: unreferenced cache pages are reclaimed (LRU) so new
    admissions never starve, and the engine keeps serving."""
    _, cfg = _model()
    rng = np.random.RandomState(23)
    # 5 allocatable pages, 3-page requests: each run caches 2 pages,
    # so the third distinct prompt MUST evict
    eng = _engine(num_pages=6, max_len=32, prefill_chunk=16)
    refs, ids = [], []
    prompts = [rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
               for _ in range(3)]
    refs = _ref_off([(p, 6) for p in prompts], num_pages=6,
                    max_len=32, prefill_chunk=16)
    for p in prompts:
        ids.append(eng.add_request(p, 6))
        by = {r.request_id: r for r in eng.run()}
    g = eng.gauges()
    assert g["prefix_cache_evictions"] >= 2
    done = {r.request_id: r for r in eng.completed}
    for rid, ref in zip(ids, refs):
        assert done[rid].tokens == ref
    _balanced(eng)


def test_audit_catches_refcount_corruption():
    """The extended invariant actually bites: a corrupted node
    refcount (or a vanished free-list page) raises the audit
    AssertionError instead of leaking quietly."""
    _, cfg = _model()
    rng = np.random.RandomState(29)
    prompt = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
    eng = _engine()
    eng.add_request(prompt, 4)
    eng.run()
    assert eng.prefix_cache_pages >= 2
    eng._audit_pages("healthy")               # sanity: green first
    node = next(iter(eng._pc_nodes.values()))
    node.ref += 1
    with pytest.raises(AssertionError, match="refcount"):
        eng._audit_pages("corrupted")
    node.ref -= 1
    eng._audit_pages("restored")


def test_warm_cache_saves_prefill_work():
    """The capacity story in miniature: the SAME shared-prefix batch
    re-run on a warm engine skips >= 50% of its prefill tokens
    (the bench storm's acceptance shape, pinned functionally)."""
    _, cfg = _model()
    rng = np.random.RandomState(31)
    shared = rng.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
    specs = [(np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size,
                             (int(rng.randint(0, 4)),)).astype(np.int32)]),
        4) for _ in range(4)]
    prompt_tokens = sum(len(p) for p, _ in specs)

    eng = _engine(num_slots=4)
    for p, n in specs:
        eng.add_request(p, n)
    eng.run()                                 # cold: populates
    cold_saved = eng.gauges()["prefix_cache_tokens_saved"]
    eng.reset_gauges()
    for p, n in specs:
        eng.add_request(p, n)
    eng.run()                                 # warm: every prefix hits
    warm = eng.gauges()
    assert warm["prefix_cache_hit_rate"] == 1.0
    assert warm["prefix_cache_tokens_saved"] > cold_saved
    assert warm["prefix_cache_tokens_saved"] >= 0.5 * prompt_tokens
    _balanced(eng)


def test_fleet_prefix_affinity_hint():
    """Same-prefix requests route to the replica that served the
    prefix last (warm cache), strictly below health/least-loaded —
    and never to an ejected replica."""
    m, cfg = _model()
    rng = np.random.RandomState(37)
    shared = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)

    def factory():
        return ContinuousBatchingEngine(
            m, num_slots=2, page_size=8, max_len=64, decode_chunk=4,
            prefill_chunk=32, greedy=True)

    fleet = ServingFleet(factory, num_replicas=3)
    h = hash(shared[:8].tobytes())
    fleet.submit(shared, 3)
    fleet.run()
    first = fleet._affinity[h]
    for _ in range(3):
        tail = rng.randint(0, cfg.vocab_size, (2,)).astype(np.int32)
        fleet.submit(np.concatenate([shared, tail]), 3)
        fleet.run()
        assert fleet._affinity[h] == first    # sticky while healthy
    assert fleet.gauges()["affinity_hits"] >= 3
    # circuit-breaker/ejection outranks affinity: the preferred
    # replica is gone, routing must silently fall elsewhere
    fleet.eject(first)
    fleet.submit(np.concatenate(
        [shared, rng.randint(0, cfg.vocab_size,
                             (2,)).astype(np.int32)]), 3)
    done = fleet.run()
    assert all(r.error is None for r in done)
    assert fleet._affinity[h] != first
