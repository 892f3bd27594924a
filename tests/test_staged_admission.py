"""Admission and eviction decide slot state on the HOST; the next
dispatch's one upload carries it and the step program applies it.

Two halves: ``_admit()`` runs no device program and no transfer (it raised
under a transfer guard while it updated device arrays slot by slot), and
the staged reset of a slot's chained ctx/active state gets every path
right — a drained slot, a device-active slot evicted and re-bound in one
turn, a prefix-cache hit that starts past 0, the copy-on-write case, a
containment rebuild — under both pumps, each stream equal to the request
served alone by dense ``generate``."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = cfg.scan_layers = False
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, slots=2, **kw):
    return ContinuousBatchingEngine(
        model, num_slots=slots, page_size=PAGE, max_len=64, decode_chunk=4,
        prefill_chunk=16, greedy=True, **kw)


def _prompt(model, seed, n):
    return np.random.RandomState(seed).randint(
        0, model.config.vocab_size, (n,)).astype(np.int32)


def _alone(model, prompt, n):
    """The oracle: the request by itself through dense greedy generate."""
    ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
    toks, _ = model.generate(ids, max_new_tokens=n,
                             decode_strategy="greedy_search",
                             eos_token_id=None, pad_token_id=0)
    return np.asarray(toks.numpy())[0].tolist()


def _pump(eng, pump):
    if pump == "run":
        return eng.run()
    done = []
    while eng.has_work():
        done.extend(eng.step())
    return done


def _at_drain(eng, turn, action):
    """Run ``action`` at the start of the engine's ``turn``-th drain: a
    point both pumps pass once a turn, before the turn's admission, and
    under ``run()`` with the pipelined successor in flight."""
    drain, seen = eng._drain, [0]

    def hooked():
        seen[0] += 1
        if seen[0] == turn:
            action()
        return drain()
    eng._drain = hooked


def _streams(model, eng, done, reqs):
    """Every request of ``reqs`` (id -> (prompt, n)) finished with the
    tokens it gets alone."""
    by = {r.request_id: r for r in done}
    for rid, (prompt, n) in reqs.items():
        assert by[rid].error is None
        assert by[rid].tokens == _alone(model, prompt, n), rid


def test_admission_runs_no_device_work(model):
    """Free slots, queued requests: ``_admit()`` binds them with no
    transfer and no new device array, and the step that follows ships
    ONE host array that carries every admitted row."""
    eng = _engine(model, slots=4)
    for seed in range(3):
        eng.add_request(_prompt(model, seed, 9), 5)
    held = {id(a) for a in jax.live_arrays()}
    with jax.transfer_guard("disallow_explicit"):
        eng._admit()
    assert {id(a) for a in jax.live_arrays()} <= held
    assert eng.gauges()["prefills"] == 3
    assert list(eng._slot_reset) == [0, 0, 0, -1]
    before = eng.gauges()
    assert before["step_uploads"] == before["staged_slot_updates"] == 0
    eng._harvest_step(eng._dispatch_turn())
    after = eng.gauges()
    assert after["step_uploads"] == 1
    assert after["staged_slot_updates"] == 3
    assert after["uploads_per_step"] == 1.0
    assert list(eng._slot_reset) == [-1] * 4      # retired at launch
    done = _pump(eng, "step")
    assert len(done) == 3 and eng.gauges()["uploads_per_step"] == 1.0


@pytest.mark.parametrize("pump", ["step", "run"])
def test_admit_into_a_drained_slot(model, pump):
    """More requests than slots: every later request enters a slot its
    predecessor drained from, whose device context still holds the
    predecessor's length until the reset lands."""
    eng = _engine(model)
    reqs = {}
    for seed, (plen, n) in enumerate([(19, 6), (5, 3), (11, 9), (4, 1),
                                      (23, 4)]):
        p = _prompt(model, seed, plen)
        reqs[eng.add_request(p, n)] = (p, n)
    done = _pump(eng, pump)
    _streams(model, eng, done, reqs)
    g = eng.gauges()
    assert g["staged_slot_updates"] == g["prefills"] == 5
    assert g["uploads_per_step"] == 1.0 and g["compiled_programs"] == 1


@pytest.mark.parametrize("pump", ["step", "run"])
def test_evict_an_active_slot_and_rebind_it_in_one_turn(model, pump):
    """Both slots decode; a higher-priority request arrives: the younger
    occupant is evicted while the DEVICE holds it active and the arrival
    takes its slot in the same admission pass, so two resets of one slot
    coalesce (the admission's wins). The bystander's stream, the
    arrival's and the victim's replay all equal the streams served
    alone."""
    eng = _engine(model)
    low = [(_prompt(model, 31, 7), 18), (_prompt(model, 32, 9), 16)]
    hi = (_prompt(model, 33, 12), 5)
    reqs = {eng.add_request(p, n): (p, n) for p, n in low}
    seen = {}

    def arrive():
        assert eng.active.all()                 # both decode on the host
        assert bool(np.asarray(eng._dev_act).all())     # and the device
        seen["victim"] = eng.slot_req[1]
        seen["hi"] = eng.add_request(*hi, priority=3)
        reqs[seen["hi"]] = hi
    _at_drain(eng, 3, arrive)
    done = _pump(eng, pump)
    _streams(model, eng, done, reqs)
    g = eng.gauges()
    assert g["preempt_evictions"] == 1
    # the arrival sits where the victim sat, bound in the turn that
    # evicted it: 4 admissions (two, the arrival, the replay) + 1 clear
    slots = {h["slot"] for r in done if r.request_id == seen["hi"]
             for h in r.hops if h["kind"] == "admit"}
    assert slots == {1} and seen["victim"].preemptions == 1
    assert g["staged_slot_updates"] == 5 and g["uploads_per_step"] == 1.0


@pytest.mark.parametrize("pump", ["step", "run"])
def test_prefix_hit_and_copy_on_write_start_past_zero(model, pump):
    """A prompt whose first two pages are cached starts at context 16,
    and one that is cached WHOLE forks its last page and starts at its
    last token: the reset carries ``start``, not 0."""
    eng = _engine(model, prefix_cache=True)
    base = _prompt(model, 41, 2 * PAGE + 3)
    reqs = {eng.add_request(base, 4): (base, 4)}
    done = _pump(eng, pump)
    tail = np.concatenate([base[:2 * PAGE], _prompt(model, 42, 5)])
    whole = base[:2 * PAGE].copy()
    other = _prompt(model, 43, 6)
    starts = {}
    admit = eng._stage_slot

    def staged(slot, req, *a, **k):
        admit(slot, req, *a, **k)
        starts[req.request_id] = int(eng._slot_reset[slot])
    eng._stage_slot = staged
    for p, n in ((other, 12), (tail, 6), (whole, 7)):
        reqs[eng.add_request(p, n)] = (p, n)
    done += _pump(eng, pump)
    _streams(model, eng, done, reqs)
    assert sorted(starts.values()) == [0, 2 * PAGE - 1, 2 * PAGE]
    g = eng.gauges()
    assert g["prefix_cache_hits"] == 2 and g["prefix_cache_cow_forks"] == 1
    assert g["uploads_per_step"] == 1.0


@pytest.mark.parametrize("pump", ["step", "run"])
def test_fresh_admissions_after_a_containment_rebuild(model, pump):
    """A harvest fails: the engine rebuilds its device state, drops the
    staged resets with it, and replays the survivors; requests that
    arrive afterwards are admitted into the rebuilt slots. Every stream
    is the stream served alone."""
    eng = _engine(model)
    first = [(_prompt(model, 51, 10), 9), (_prompt(model, 52, 6), 11)]
    later = [(_prompt(model, 53, 17), 5), (_prompt(model, 54, 5), 7)]
    reqs = {eng.add_request(p, n): (p, n) for p, n in first}
    harvest, calls = eng._harvest_step, [0]

    def failing(rec):
        calls[0] += 1
        if calls[0] == 2:
            for p, n in later:
                reqs[eng.add_request(p, n)] = (p, n)
            raise RuntimeError("injected step failure")
        return harvest(rec)
    eng._harvest_step = failing
    done = _pump(eng, pump)
    _streams(model, eng, done, reqs)
    g = eng.gauges()
    assert g["containments"] == 1 and g["quarantined"] == 0
    assert g["uploads_per_step"] == 1.0
    assert list(eng._slot_reset) == [-1, -1] and eng._staged_rows == 0
