"""Both drivers end to end on the CPU at a tiny size, up to the shape of
the last line; the faults a cell can have, planted under the timed path,
make ``correct`` come out false; and the control, at a size a test run can
hold, fails."""

import json

import numpy as np
import pytest

from conftest import drive, load_cfg, load_traffic

LINE_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def shape_ok(res, metric):
    assert list(res) == LINE_KEYS                      # checks come last
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["metrics"]) == {metric, "setup_s"}
    for v in res["metrics"].values():
        assert v["value"] > 0 and isinstance(v["unit"], str)
    for c in res["checks"].values():
        assert "value" in c and ("limit" in c or "limit_min" in c)
    json.dumps(res)


def test_closed_loop_serving(bench):
    tr = load_traffic("tiny_closed.json")
    res = drive(bench, load_cfg("tiny-qwen2.json"), tr, 0)
    shape_ok(res, "serve_tok_s")
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] >= tr["clients"]
    assert res["checks"]["served_gap_max"]["value"] <= 1e-3


def test_closed_loop_holds_its_client_count():
    """Every client has exactly one request in the engine at any time."""
    from perfbench.harness import serve, tracing

    class Engine:                      # two tokens a step, three steps each
        def __init__(self):
            self.reqs, self.next, self.peak = {}, 0, 0

        def add_request(self, prompt, max_new):
            self.next += 1
            self.reqs[self.next] = type("R", (), dict(
                request_id=self.next, tokens=[], t_admit=0.0, error=None,
                max_new=max_new))()
            return self.next

        def request(self, rid):
            return self.reqs[rid]

        def has_work(self):
            return bool(self.reqs)

        def step(self):
            self.peak = max(self.peak, len(self.reqs))
            done = []
            for rid, r in list(self.reqs.items()):
                r.tokens += [1, 1]
                if len(r.tokens) >= 6:
                    done.append(self.reqs.pop(rid))
            return done

        def gauges(self):
            return {}

    tr = load_traffic("tiny_closed.json")
    eng = Engine()
    cfg = {"sizes": {"vocab_size": 256}}
    win = serve.run_window(eng, cfg, tr, 1, 0.05, None,
                           tracing.annotator(False))
    assert eng.peak == tr["clients"] and len(eng.reqs) == tr["clients"]
    by_client = {}
    for r in win.recs:
        by_client.setdefault(r.client, []).append(r)
    assert sorted(by_client) == list(range(tr["clients"]))
    for recs in by_client.values():
        assert sum(1 for r in recs if not r.done) == 1
    assert serve.tokens_in(win, win.t_start, win.t_end) == 2 * len(win.turns) \
        * tr["clients"]


def test_open_loop_serving(bench):
    res = drive(bench, load_cfg("tiny-qwen2.json"),
                load_traffic("tiny_open.json"), 0)
    shape_ok(res, "serve_tok_s")
    assert res["correct"]


def test_a_token_altered_where_it_is_produced_is_not_correct(bench,
                                                            monkeypatch):
    from perfbench.harness import serve
    real = serve.build_engine

    def faulty(cfg, model):
        eng = real(cfg, model)
        step = eng.step

        def bad_step():
            done = step()
            for r in done:              # one token of every finished stream
                if len(r.tokens) > 2:
                    r.tokens[1] = (r.tokens[1] + 1) % cfg["sizes"][
                        "vocab_size"]
            return done

        eng.step = bad_step
        return eng

    monkeypatch.setattr(serve, "build_engine", faulty)
    res = drive(bench, load_cfg("tiny-qwen2.json"),
                load_traffic("tiny_closed.json"), 0)
    assert res["correct"] is False
    c = res["checks"]["served_gap_max"]
    assert c["value"] > c["limit"]


def test_training(bench):
    res = drive(bench, load_cfg("tiny-gpt2.json"),
                load_traffic("tiny_train.json"), 1)
    shape_ok(res, "train_tok_s")
    assert res["correct"], res["checks"]
    assert res["checks"]["loss_rel_max"]["value"] < 1e-3


def _plant(monkeypatch, wrap):
    from perfbench.harness import train
    real = train.build_step

    def faulty(cfg, model):
        opt, step = real(cfg, model)
        return wrap(opt, step, model)

    monkeypatch.setattr(train, "build_step", faulty)


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(bench,
                                                              monkeypatch):
    def wrap(opt, step, model):
        def frozen(ids):
            before = [p._data for p in model.parameters()]
            loss = step(ids)
            for p, b in zip(model.parameters(), before):
                p._data = b
            return loss
        return opt, frozen

    _plant(monkeypatch, wrap)
    res = drive(bench, load_cfg("tiny-gpt2.json"),
                load_traffic("tiny_train.json"), 1)
    assert res["correct"] is False
    c = res["checks"]["update_norm_gap_max"]
    assert c["value"] == pytest.approx(1.0, abs=1e-3)
    assert c["value"] > c["limit"]


def test_half_the_batch_left_out_is_not_correct(bench, monkeypatch):
    def wrap(opt, step, model):
        return opt, lambda ids: step(ids[:ids.shape[0] // 2])

    _plant(monkeypatch, wrap)
    res = drive(bench, load_cfg("tiny-gpt2.json"),
                load_traffic("tiny_train.json"), 1)
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values()
               if "limit" in c)


def test_a_fault_in_the_compiled_step_alone_is_not_correct(bench,
                                                          monkeypatch):
    """The first step of a ``to_static`` function runs eagerly; the window
    times the program compiled at the second. A backward that is wrong in
    that program only (here: half of the batch, from step 2 on) leaves the
    first gradient sound and fails the second."""
    def wrap(opt, step, model):
        calls = []

        def later_half(ids):
            calls.append(1)
            return step(ids if len(calls) == 1 else ids[:ids.shape[0] // 2])
        return opt, later_half

    _plant(monkeypatch, wrap)
    res = drive(bench, load_cfg("tiny-gpt2.json"),
                load_traffic("tiny_train.json"), 1)
    assert res["correct"] is False, res["checks"]
    g1, g2 = (res["checks"][k] for k in ("grad_norm_gap_max",
                                         "grad2_norm_gap_max"))
    assert g1["value"] <= g1["limit"] and g2["value"] > g2["limit"]


def test_flop_readers_read_nothing_where_the_prefix_cache_served():
    """``work_items`` counts every prompt token as computed; a window in
    which the cache served some has no FLOP count to give."""
    from perfbench.harness import readers, serve
    win = serve.Window(t_start=0.0, t_end=1.0,
                       gauges={"prefix_cache_hits": 3})
    ctx = {"win": win, "chunk": 16, "cfg": load_cfg("tiny-qwen2.json"),
           "reduced": {"window": (0.0, 1.0), "steps": 1}, "span": (0.0, 1.0)}
    assert readers.read_serve_mfu({}, ctx) is None
    win.gauges = {"prefix_cache_hits": 0}
    assert readers.read_serve_mfu({}, ctx) is None      # nothing sampled


def test_harness_stats_of_an_open_window():
    from perfbench.harness import readers, serve
    win = serve.Window(turns=[(0.0, 0.5), (0.5, 1.1), (1.1, 1.8)])
    for i, (due, admit) in enumerate([(0.0, 0.1), (0.2, 0.5), (0.4, 1.1)]):
        h = type("H", (), {"t_admit": admit})()
        win.recs.append(serve.Rec(i, due, due, np.zeros(4, np.int32), 4, h))
    ctx = {"win": win}
    assert readers.read_harness_stat(
        {"stat": "queue_wait_ms", "percentile": 50}, ctx) == \
        pytest.approx(300.0)
    assert readers.read_harness_stat(
        {"stat": "turn_ms", "percentile": 50}, ctx) == pytest.approx(600.0)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_control_fails_at_test_size(seed):
    """The reference in fp8 put in the program's place: the token it puts
    first lies further below the reference's best than the limit allows."""
    from perfbench.harness import precision, serve
    cfg = load_cfg("tiny-qwen2.json")
    rng = np.random.default_rng(seed)
    k, T_, R = 3, 64, 24
    ids = rng.integers(0, 256, (k, T_)).astype(np.int32)
    pos = np.tile(np.arange(16, 16 + R, dtype=np.int32), (k, 1))
    tok = np.zeros((k, R), np.int32)
    mask = np.ones((k, R), bool)
    _, arg_c = serve.served_gaps(cfg, seed, ids, pos, tok,
                                 mm=precision.mm_fp8)
    ok, out, arg = serve.judge(cfg, seed, 0, ids, pos, arg_c, mask)
    assert ok is False                  # judged as a run's tokens are
    assert out["served_gap_max"]["value"] > out["served_gap_max"]["limit"]
    ok, out, _ = serve.judge(cfg, seed, 0, ids, pos, arg, mask)
    assert ok and out["served_gap_max"]["value"] == 0.0   # the reference


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_control_and_fault_fail_at_test_size(seed):
    from perfbench.harness import precision, train, traffic as T
    cfg, tr = load_cfg("tiny-gpt2.json"), load_traffic("tiny_train.json")
    cfg["check"] = {"loss_rel": 1e-4, "grad_norm_gap": 0.02,
                    "grad2_norm_gap": 0.02, "update_norm_gap": 0.5}
    _, names, _ = train.pieces(cfg)
    gen = T.train_batches(tr, seed)
    batches = [next(gen) for _ in range(3)]
    ref, w0 = train.reference_readings(cfg, tr, seed, batches)
    same, _ = train.reference_readings(cfg, tr, seed, batches, w0=w0)
    ok, _ = train.compare(cfg, names, same, ref)
    assert ok
    for kw in ({"mm": precision.mm_fp8},
               {"batch_fault": lambda ids: ids[:1]}):
        got, _ = train.reference_readings(cfg, tr, seed, batches, w0=w0, **kw)
        ok, out = train.compare(cfg, names, got, ref)
        assert not ok, out
    # half of the batch left out fails the gradient of the second step too
    assert out["grad2_norm_gap_max"]["value"] > 0.02
