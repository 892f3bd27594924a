"""Disaggregated prefill/decode serving (ISSUE 17) — fast tier.

The migration primitive in isolation (export → import round trip on
single engines: the satellite's "pages out, pages back in, token
identity + audit green"), the payload codec, the degradation paths
(corrupt blocks, geometry mismatch, no decode capacity), and the
in-process :class:`DisaggServingFleet` end to end. Process-backed
chaos lives in test_disagg_chaos.py (slow tier; the ``disagg_chaos``
gate runs both).
"""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  DisaggServingFleet)
from paddle_tpu.inference.disagg import (kv_payload_from_wire,
                                         kv_payload_nbytes,
                                         kv_payload_to_wire)
from paddle_tpu.inference.reliability import salvage_unfinished
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.disagg

os.environ.setdefault("PADDLE_TPU_SERVING_AUDIT", "1")

_ENG_KW = dict(num_slots=2, page_size=8, max_len=64, decode_chunk=4,
               prefill_chunk=32, greedy=True)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    cfg.num_hidden_layers = 2
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return cfg, m


def _specs(cfg, seed=7):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32), k)
            for n, k in [(19, 5), (24, 6), (9, 4), (17, 1), (30, 5)]]


@pytest.fixture(scope="module")
def oracle(model):
    """Colocated greedy token streams for ``_specs`` — the identity
    reference every disaggregated run must reproduce exactly."""
    cfg, m = model
    eng = ContinuousBatchingEngine(m, **_ENG_KW)
    ids = [eng.add_request(p, n) for p, n in _specs(cfg)]
    by = {r.request_id: r for r in eng.run()}
    return [by[i].tokens for i in ids]


def _drive_pair(pre, dec, n_reqs, turns=500):
    """Drive a prefill engine + decode engine with a manual pump;
    returns completions by request id and the migration count."""
    done, migrated = {}, 0
    for _ in range(turns):
        for r in pre.step():
            done[r.request_id] = r
        for req, payload in pre.take_migrations():
            out = dec.import_migration(req, payload)
            assert out["rejected"] == 0, out
            assert pre.release_exported(req.request_id)
            migrated += 1
        for r in dec.step():
            done[r.request_id] = r
        if len(done) == n_reqs and not pre.has_work() \
                and not dec.has_work():
            return done, migrated
    raise AssertionError(f"did not converge: {len(done)}/{n_reqs}")


# ---- the migration primitive in isolation ------------------------------

def test_handoff_reattach_round_trip_single_engine(model):
    """The satellite pin: ``handoff()`` mid-stream takes every page
    out, ``requeue`` puts them back on the SAME engine, and the
    resumed stream is byte-identical with a green audit."""
    cfg, m = model
    specs = [(p, n + 12) for p, n in _specs(cfg)]  # long streams
    eng = ContinuousBatchingEngine(m, **_ENG_KW)
    ids = [eng.add_request(p, n) for p, n in specs]
    # fresh oracle (separate engine, uncontended ordering)
    oracle_eng = ContinuousBatchingEngine(m, **_ENG_KW)
    oids = [oracle_eng.add_request(p, n) for p, n in specs]
    oby = {r.request_id: r for r in oracle_eng.run()}
    ref = {i: oby[o].tokens for i, o in zip(ids, oids)}

    done = {}
    for _ in range(200):                  # mid-stream: some tokens out
        for r in eng.step():
            done[r.request_id] = r
        live = [r for r in eng.slot_req if r is not None]
        if any(r.tokens for r in live):
            break
    parked = eng.handoff()
    assert parked, "handoff drained nothing mid-stream"
    assert not eng.has_work()
    eng._audit_pages("post-handoff")      # pages all the way out
    for req in parked:
        eng.requeue(req)                  # pages back in (recompute)
    done.update({r.request_id: r for r in eng.run()})
    by = done
    for i in ids:
        assert by[i].tokens == ref[i], (i, by[i].tokens, ref[i])
    eng._audit_pages("post-reattach")


def test_migration_token_identity_single_pair(model, oracle):
    """Export from a prefill-role engine, import into a decode-role
    engine: greedy streams token-identical to colocated, audits green
    both sides, single-token requests complete locally."""
    cfg, m = model
    pre = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    dec = ContinuousBatchingEngine(m, role="decode", **_ENG_KW)
    ids = [pre.add_request(p, n) for p, n in _specs(cfg)]
    done, migrated = _drive_pair(pre, dec, len(ids))
    for i, ref in zip(ids, oracle):
        assert done[i].tokens == ref, i
    assert migrated == 4        # the max_new=1 request stays local
    assert pre._c_migrated_out.value == 4
    assert dec._c_kv_imported.value > 0
    assert dec._c_kv_rejects.value == 0
    pre._audit_pages("test")
    dec._audit_pages("test")
    hops = [h["kind"] for h in done[ids[0]].hops]
    assert "migrate_out" in hops and "migrate_in" in hops, hops


def test_import_back_into_source_engine(model, oracle):
    """Pages out and back in on ONE engine: export, release, then
    import into the exporting engine itself — the tightest loop over
    the primitive (dedup against its own still-cached chain is
    legal; the stream must stay identical either way)."""
    cfg, m = model
    eng = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    prompt, n_new = _specs(cfg)[0]
    rid = eng.add_request(prompt, n_new)
    for _ in range(200):
        eng.step()
        if eng.migrations_out:
            break
    (req, payload), = eng.take_migrations()
    assert eng.release_exported(req.request_id)
    req.no_migrate = True          # complete colocated after re-entry
    out = eng.import_migration(req, payload)
    assert out["rejected"] == 0
    done = {r.request_id: r for r in eng.run()}
    assert done[rid].tokens == oracle[0]
    eng._audit_pages("test")


def _export_one(model, spec=1, **kw):
    cfg, m = model
    pre = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW, **kw)
    prompt, n_new = _specs(cfg)[spec]
    rid = pre.add_request(prompt, n_new)
    for _ in range(200):
        pre.step()
        if pre.migrations_out:
            break
    (req, payload), = pre.take_migrations()
    return pre, rid, req, payload


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_exported_pages_are_the_pools_own_rows(model, kv_quant):
    """Payload version 2: a block's arrays are the pools' pages as they
    lie — ``[page_size, kv_heads * head_dim]``, scales ``[kv_heads,
    page_size]`` — and an import lands them, through the wire codec,
    bit for bit at the page axis (0) of the destination's pools."""
    from paddle_tpu.inference.serving import KV_PAYLOAD_VERSION
    cfg, m = model
    pre, rid, req, payload = _export_one(model, kv_quant=kv_quant)
    assert payload["version"] == KV_PAYLOAD_VERSION == 2
    kvh = cfg.num_key_value_heads
    d = cfg.hidden_size // cfg.num_attention_heads
    want = [(8, kvh * d)] * 2 + ([(kvh, 8)] * 2 if kv_quant == "int8"
                                 else [])
    for blk in payload["blocks"]:
        assert [a.shape for a in blk["data"]] \
            == want * cfg.num_hidden_layers
    dec = ContinuousBatchingEngine(m, role="decode", **_ENG_KW,
                                   kv_quant=kv_quant)
    out = dec.import_migration(req, kv_payload_from_wire(
        kv_payload_to_wire(payload)))
    assert out == {"imported": len(payload["blocks"]), "dedup": 0,
                   "rejected": 0}
    node = dec._pc_root
    for blk in payload["blocks"]:
        node = node.children[np.asarray(blk["tokens"], np.int32).tobytes()]
        for pool, data in zip(dec._paged_arrays(), blk["data"]):
            np.testing.assert_array_equal(np.asarray(pool[node.page]),
                                          data)
    pre.release_exported(req.request_id)
    assert len({r.request_id: r for r in dec.run()}[rid].tokens) \
        == _specs(cfg)[1][1]
    dec._audit_pages("test")


def test_an_old_payload_version_is_refused_by_name(model, oracle):
    """A version-1 payload (pages shipped ``[kv_heads, page_size,
    head_dim]``) lands nothing, the result names the version, and the
    request replays from its tokens to the identical stream."""
    cfg, m = model
    pre, rid, req, payload = _export_one(model)
    dec = ContinuousBatchingEngine(m, role="decode", **_ENG_KW)
    out = dec.import_migration(req, dict(payload, version=1))
    assert (out["imported"], out["dedup"], out["rejected"]) == (0, 0, 0)
    assert "version 1" in out["refused"] and "version 2" in out["refused"]
    assert dec.prefix_cache_pages == 0
    pre.release_exported(req.request_id)
    done = {r.request_id: r for r in dec.run()}
    assert done[rid].tokens == oracle[1]
    dec._audit_pages("test")


def test_salvage_includes_parked_migrations(model):
    """An engine dying between parking a migration and its pickup
    must surface the parked request to ``salvage_unfinished`` — the
    prefill-death-mid-transfer guarantee at the engine tier."""
    cfg, m = model
    eng = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    prompt, n_new = _specs(cfg)[0]
    rid = eng.add_request(prompt, n_new)
    for _ in range(200):
        eng.step()
        if eng.migrations_out:
            break
    assert eng.migrations_out
    salvaged = salvage_unfinished(eng)
    assert rid in [r.request_id for r in salvaged]


# ---- degradation paths -------------------------------------------------

def test_corrupt_block_rejected_stream_still_identical(model, oracle):
    """A damaged KV block fails its crc at import: seeding stops at
    the bad page, the request replays the rest from its prompt, and
    the stream stays token-identical (correctness never trusted the
    transfer)."""
    cfg, m = model
    pre = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    dec = ContinuousBatchingEngine(m, role="decode", **_ENG_KW)
    prompt, n_new = _specs(cfg)[1]        # 24 tokens -> 3 full pages
    rid = pre.add_request(prompt, n_new)
    for _ in range(200):
        pre.step()
        if pre.migrations_out:
            break
    (req, payload), = pre.take_migrations()
    blk = payload["blocks"][1]["data"][0]
    flat = np.asarray(blk).reshape(-1).copy()
    flat[0] = flat[0] + 1                 # flip one element
    payload["blocks"][1]["data"][0] = flat.reshape(np.asarray(blk).shape)
    out = dec.import_migration(req, payload)
    assert out["rejected"] == 1
    assert out["imported"] == 1           # block 0 landed, then stop
    assert dec._c_kv_rejects.value == 1
    pre.release_exported(req.request_id)
    done = {r.request_id: r for r in dec.run()}
    assert done[rid].tokens == oracle[1]
    dec._audit_pages("test")


def test_geometry_mismatch_falls_back_to_replay(model, oracle):
    """A payload whose page_size/dtype/pool-count doesn't match the
    destination imports nothing — plain prompt replay, identical
    stream."""
    cfg, m = model
    pre = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    dec = ContinuousBatchingEngine(m, role="decode", **_ENG_KW)
    prompt, n_new = _specs(cfg)[0]
    rid = pre.add_request(prompt, n_new)
    for _ in range(200):
        pre.step()
        if pre.migrations_out:
            break
    (req, payload), = pre.take_migrations()
    payload = dict(payload, page_size=payload["page_size"] * 2)
    out = dec.import_migration(req, payload)
    assert out == {"imported": 0, "dedup": 0, "rejected": 0}
    pre.release_exported(req.request_id)
    done = {r.request_id: r for r in dec.run()}
    assert done[rid].tokens == oracle[0]
    dec._audit_pages("test")


def test_codec_round_trip_and_damage_tolerance(model):
    cfg, m = model
    pre = ContinuousBatchingEngine(m, role="prefill", **_ENG_KW)
    pre.add_request(_specs(cfg)[0][0], 5)
    for _ in range(200):
        pre.step()
        if pre.migrations_out:
            break
    (_, payload), = pre.take_migrations()
    wire = kv_payload_to_wire(payload)
    back = kv_payload_from_wire(wire)
    assert back["dtype"] == payload["dtype"]
    assert back["eff_len"] == payload["eff_len"]
    assert kv_payload_nbytes(back) == kv_payload_nbytes(payload)
    for a, b in zip(back["blocks"], payload["blocks"]):
        assert list(a["tokens"]) == list(b["tokens"])
        assert a["crc"] == b["crc"]
        for x, y in zip(a["data"], b["data"]):
            assert x.tobytes() == np.ascontiguousarray(y).tobytes()
    # malformed wire form degrades to zero blocks, never raises
    bad = dict(wire, blocks=[{"tokens": [1], "data": ["!!"],
                              "crc": [0]}])
    assert kv_payload_from_wire(bad)["blocks"] == []


def test_role_validation(model):
    cfg, m = model
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(m, role="prefil", **_ENG_KW)


# ---- the in-process fleet ----------------------------------------------

def test_fleet_disagg_token_identity_and_metrics(model, oracle):
    """1 prefill + 1 decode in-proc replicas: identical streams, the
    migration leg on the hop timeline, federated ``disagg/*`` metrics
    moving, role gauges, audits green on both replicas."""
    cfg, m = model

    def factory(role="both"):
        return ContinuousBatchingEngine(m, role=role, **_ENG_KW)

    fleet = DisaggServingFleet(factory, num_prefill=1, num_decode=1,
                               hedge_delay_s=None)
    fids = [fleet.submit(p, n) for p, n in _specs(cfg)]
    done = {r.request_id: r for r in fleet.run()}
    for fid, ref in zip(fids, oracle):
        assert done[fid].error is None, done[fid].error
        assert done[fid].tokens == ref, (fid,)
    assert fleet.metrics.counter("disagg/migrations").value == 4
    assert fleet.metrics.counter(
        "disagg/migration_failures").value == 0
    assert fleet.metrics.counter("disagg/kv_bytes_moved").value > 0
    hops = [h["kind"] for h in done[fids[0]].hops]
    assert "migrate" in hops, hops          # the fleet-recorded leg
    assert hops.index("migrate_out") < hops.index("migrate_in"), hops
    g = fleet.gauges()
    assert g["roles"] == {0: "prefill", 1: "decode"}
    assert g["migrations"] == 4 and g["migration_ms_p99"] > 0
    for rep in fleet.replicas.values():
        rep.engine._audit_pages("test")
    # per-role SLO surface: quotes exist once history does
    assert fleet.predicted_itl_s() is None \
        or fleet.predicted_itl_s() > 0


def test_prefill_scale_up_warms_the_step_program(model):
    """ISSUE-19 satellite: a warm ``scale_up(role="prefill")`` must
    compile the engine's step program before the replica takes router
    weight — a long prompt served right after the scale-up must not
    pay an XLA compile inside the serving path."""
    cfg, m = model
    kw = dict(_ENG_KW, prefill_chunk=32)

    def factory(role="both"):
        return ContinuousBatchingEngine(m, role=role, **kw)

    fleet = DisaggServingFleet(factory, num_prefill=1, num_decode=1,
                               hedge_delay_s=None)
    rid = fleet.scale_up(role="prefill", warm=True)
    eng = fleet.replicas[rid].engine
    assert any(sig[1] == 32 for sig in eng._compiled
               if sig[0] == "unified"), eng._compiled
    before = eng.gauges()["compiled_programs"]
    # a long prompt straight onto the warmed engine: zero new
    # compiled signatures
    prompt = np.arange(28, dtype=np.int32) % cfg.vocab_size
    eng.add_request(prompt, 1)
    for _ in range(200):
        if not fleet.replicas[rid].has_work():
            break
        fleet.replicas[rid].step()
    assert not fleet.replicas[rid].has_work()
    assert eng.gauges()["compiled_programs"] == before
    fleet.close()


def test_fleet_no_decode_capacity_degrades_colocated(model, oracle):
    """Decode-fleet outage: migrations fail (no candidate), requests
    pin ``no_migrate`` and complete COLOCATED on the prefill replica
    — identical streams, no livelock, failures counted."""
    cfg, m = model

    def factory(role="both"):
        return ContinuousBatchingEngine(m, role=role, **_ENG_KW)

    fleet = DisaggServingFleet(factory, num_prefill=1, num_decode=0,
                               hedge_delay_s=None)
    fids = [fleet.submit(p, n) for p, n in _specs(cfg)]
    done = {r.request_id: r for r in fleet.run()}
    for fid, ref in zip(fids, oracle):
        assert done[fid].error is None, done[fid].error
        assert done[fid].tokens == ref, (fid,)
    assert fleet.metrics.counter(
        "disagg/migration_failures").value >= 1
    assert fleet.metrics.counter("disagg/migrations").value == 0
    fleet.replicas[0].engine._audit_pages("test")


def test_fleet_both_roles_is_plain_fleet(model, oracle):
    """role="both" everywhere == the base fleet: no migrations, same
    streams — DisaggServingFleet degenerates cleanly."""
    cfg, m = model

    def factory(role="both"):
        return ContinuousBatchingEngine(m, role=role, **_ENG_KW)

    fleet = DisaggServingFleet(factory, num_prefill=0, num_decode=0,
                               hedge_delay_s=None)
    fleet.add_role_replica("both")
    fids = [fleet.submit(p, n) for p, n in _specs(cfg)]
    done = {r.request_id: r for r in fleet.run()}
    for fid, ref in zip(fids, oracle):
        assert done[fid].tokens == ref
    assert fleet.metrics.counter("disagg/migrations").value == 0
