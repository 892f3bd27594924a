"""The serving step is given its pools: they are persistable state the
step reassigns, so ``to_static`` donates their buffers and the program
writes the new pools into them.

Two halves: across turns the engine keeps the SAME pool Tensors, each
compiled step adds the pool count to ``jit/donated_inputs`` and deletes
the arrays the pools held before it; and every path that replaces a pool
between steps — a prefix-cache copy-on-write fork, a migration import, a
containment rebuild — rebinds those Tensors, so the next compiled step
reads the current pools and each stream equals the request served alone
by dense ``generate``."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler.metrics import get_registry

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = cfg.scan_layers = False
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _engine(model, **kw):
    return ContinuousBatchingEngine(
        model, num_slots=2, page_size=PAGE, max_len=64, decode_chunk=4,
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


def _pump(eng):
    done = []
    while eng.has_work():
        done.extend(eng.step())
    return done


def _streams(model, done, reqs):
    by = {r.request_id: r for r in done}
    for rid, (prompt, n) in reqs.items():
        assert by[rid].error is None
        assert by[rid].tokens == _alone(model, prompt, n), rid


def test_pools_stay_and_each_step_consumes_their_arrays(model):
    """Turn after turn: the same pool Tensors, ``len(pools)`` buffers
    donated per compiled call of the step, and a pool array read before
    a step is deleted by it (discovery, the first turn, runs eagerly and
    donates nothing)."""
    eng = _engine(model)
    reqs = {}
    for seed, (plen, n) in enumerate([(9, 7), (21, 5), (4, 9)]):
        p = _prompt(model, seed, plen)
        reqs[eng.add_request(p, n)] = (p, n)
    pools = list(eng.pools)
    donated = get_registry().counter("jit/donated_inputs")
    done, compiled_turns = [], 0
    while eng.has_work():
        fn = eng._unified_fn
        runs = fn.n_compiled_runs if fn is not None else 0
        held = [p._data for p in eng.pools]
        before = donated.value
        done.extend(eng.step())
        ran = eng._unified_fn.n_compiled_runs - runs
        assert all(a is b for a, b in zip(eng.pools, pools))
        assert donated.value - before == ran * len(pools)
        assert all(a.is_deleted() for a in held) == bool(ran)
        compiled_turns += ran
    assert compiled_turns >= 3 and eng._unified_fn.n_eager_runs == 1
    _streams(model, done, reqs)


def _cow_fork(model, eng, reqs):
    """A prompt cached whole forks its last shared page."""
    base = _prompt(model, 41, 2 * PAGE + 3)
    reqs[eng.add_request(base, 4)] = (base, 4)
    done = _pump(eng)
    whole = base[:2 * PAGE].copy()
    reqs[eng.add_request(whole, 7)] = (whole, 7)
    return done, lambda: eng.gauges()["prefix_cache_cow_forks"] == 1


def _migration_import(model, eng, reqs):
    """A prefill-role engine exports a finished prompt's pages and this
    engine imports them into its pools."""
    first = _prompt(model, 61, 11)
    reqs[eng.add_request(first, 5)] = (first, 5)
    done = _pump(eng)
    pre = _engine(model, role="prefill")
    moved = _prompt(model, 62, 3 * PAGE + 2)
    rid = pre.add_request(moved, 6)
    while not pre.migrations_out:
        pre.step()
    (req, payload), = pre.take_migrations()
    assert eng.import_migration(req, payload)["imported"] > 0
    reqs[rid] = (moved, 6)
    return done, lambda: eng._c_kv_imported.value > 0


def _containment(model, eng, reqs):
    """A harvest fails: the engine rebuilds its pools from scratch and
    replays the survivors."""
    for seed, (plen, n) in ((71, (10, 9)), (72, (6, 11))):
        p = _prompt(model, seed, plen)
        reqs[eng.add_request(p, n)] = (p, n)
    done = _pump(eng)                      # the step compiled first
    for seed, (plen, n) in ((73, (17, 5)), (74, (5, 7))):
        p = _prompt(model, seed, plen)
        reqs[eng.add_request(p, n)] = (p, n)
    harvest, calls = eng._harvest_step, [0]

    def failing(rec):
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("injected step failure")
        return harvest(rec)
    eng._harvest_step = failing
    return done, lambda: eng.gauges()["containments"] == 1


@pytest.mark.parametrize("event", [_cow_fork, _migration_import,
                                   _containment],
                         ids=["cow_fork", "migration_import", "containment"])
def test_the_next_step_reads_the_current_pools(model, event):
    """Each path that replaces a pool between steps rebinds the engine's
    pool Tensors: the step program compiled before it keeps running
    compiled (no second discovery), reads the pools as they are now, and
    every stream equals the request served alone."""
    eng = _engine(model, prefix_cache=True)
    pools, reqs = list(eng.pools), {}
    done, happened = event(model, eng, reqs)
    runs = eng._unified_fn.n_compiled_runs
    done += _pump(eng)
    assert happened()
    assert all(a is b for a, b in zip(eng.pools, pools))
    assert eng._unified_fn.n_compiled_runs > runs
    assert eng._unified_fn.n_eager_runs == 1
    _streams(model, done, reqs)
