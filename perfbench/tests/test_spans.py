"""The readers of the program's own spans and counters
(``harness/spans.py``): on hand-built traces, and the metric files' regexes
against the spans a tiny engine and a tiny ``to_static`` step really write
(captured on the CPU: names and nesting, never a time)."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import spans, spec, xplane
from perfbench.harness.xplane import Ev, Trace

SERVE = ["pump.idle_ms_dispatch.batch", "pump.idle_ms_harvest.batch",
         "sched.idle_ms_admit_drain.batch", "pump.idle_ms_other.batch"]
TRAIN = ["step.idle_ms_execute.train", "step.idle_ms_other.train"]


def read(name, ctx):
    r = spec.load_metric_reader(name)
    return spec.resolve_reader(r)(r, ctx)


def ctx_of(tr, window, steps):
    return {"trace": tr, "reduced": {"window": window, "steps": steps}}


def turn(t, host):
    """One serving turn of 10 s from ``t`` as the program writes it."""
    host += [Ev("bench/engine.step", t, t + 10.0),
             Ev("serving/step", t + 0.1, t + 9.9),
             Ev("serving/admit", t + 0.2, t + 1.0),
             Ev("serving/dispatch", t + 1.0, t + 4.0),
             Ev("serving/dispatch.stage", t + 1.1, t + 2.0),
             Ev("serving/dispatch.launch", t + 2.5, t + 3.9),
             Ev("to_static/call", t + 2.6, t + 3.8),
             Ev("serving/harvest", t + 4.0, t + 8.0),
             Ev("serving/harvest.fetch", t + 4.0, t + 7.0),
             Ev("serving/drain", t + 8.5, t + 9.5)]


def test_overlap_of_interval_lists():
    a = [(0.0, 2.0), (3.0, 5.0), (9.0, 10.0)]
    b = [(1.0, 4.0), (4.5, 9.5)]
    assert spans._overlap(a, b) == pytest.approx(1.0 + 1.0 + 0.5 + 0.5)
    assert spans._overlap(a, []) == 0.0
    assert spans._overlap(b, a) == spans._overlap(a, b)


def test_a_gap_inside_straddling_and_under_no_span():
    host, dev = [], []
    turn(0.0, host)
    # device busy except: [1.5, 2.5] wholly inside dispatch; [3.5, 4.5]
    # straddling dispatch and harvest; [8.1, 8.4] under neither (between
    # harvest and drain); [9.6, 10.0] after drain, in the caller's code
    dev = [Ev("op", 0.0, 1.5), Ev("op", 2.5, 3.5), Ev("op", 4.5, 8.1),
           Ev("op", 8.4, 9.6)]
    tr = Trace(device_ops={0: dev}, host=sorted(host, key=lambda e: e.start))
    c = ctx_of(tr, (0.0, 10.0), 1)
    assert read(SERVE[0], c) == pytest.approx(1e3 * (1.0 + 0.5))
    assert read(SERVE[1], c) == pytest.approx(1e3 * 0.5)
    assert read(SERVE[2], c) == pytest.approx(0.0)
    assert read(SERVE[3], c) == pytest.approx(1e3 * (0.3 + 0.4))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_disjoint_classes_and_other_add_up_to_the_idle_time(seed):
    rng = np.random.default_rng(seed)
    host, dev, steps = [], [], 4
    for k in range(steps):
        turn(10.0 * k, host)
    edges = np.sort(rng.uniform(0.0, 10.0 * steps, 40))
    dev = [Ev("op", float(a), float(b))
           for a, b in zip(edges[0::2], edges[1::2])]
    tr = Trace(device_ops={0: dev, 1: [Ev("op", 0.0, 40.0)]},
               host=sorted(host, key=lambda e: (e.start, -e.end)))
    window = (3.0, 38.0)
    c = ctx_of(tr, window, steps)
    idle = sum(b - a for a, b in xplane.gaps(dev, *window))
    parts = [read(n, c) for n in SERVE]
    assert all(p is not None and p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(1e3 * idle / steps, rel=1e-12)


def test_the_training_pair_adds_up_too():
    host = []
    for k in range(3):
        t = 5.0 * k
        host += [Ev("bench/train.step", t, t + 5.0),
                 Ev("to_static/call", t + 0.1, t + 3.0),
                 Ev("to_static/bind", t + 0.1, t + 0.2),
                 Ev("to_static/execute", t + 0.2, t + 2.5),
                 Ev("to_static/commit", t + 2.5, t + 3.0)]
    dev = [Ev("op", 5.0 * k + 2.0, 5.0 * k + 4.5) for k in range(3)]
    tr = Trace(device_ops={0: dev}, host=host)
    c = ctx_of(tr, (0.0, 15.0), 3)
    ex, other = read(TRAIN[0], c), read(TRAIN[1], c)
    assert ex == pytest.approx(1e3 * 1.8)       # [0.2, 2.0] of each step
    assert other == pytest.approx(1e3 * (0.2 + 0.5))
    assert ex + other == pytest.approx(1e3 * (15.0 - 7.5) / 3)


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_none_where_the_program_wrote_no_such_span(name):
    """A parent commit from before the spans existed: bench/ spans and the
    runtime's own events only."""
    host = [Ev("bench/engine.step", 0.0, 10.0), Ev("bench/train.step", 0, 10),
            Ev("PjitFunction(ustep)", 1.0, 3.0), Ev("Allocate", 1.0, 2.0)]
    tr = Trace(device_ops={0: [Ev("op", 0.0, 5.0)]}, host=host)
    assert read(name, ctx_of(tr, (0.0, 10.0), 1)) is None
    assert read(name, {"kind": "serve"}) is None        # an untraced run
    assert read(name, ctx_of(Trace(host=host), (0.0, 10.0), 1)) is None
    assert read(name, ctx_of(tr, (0.0, 10.0), 0)) is None


def test_registry_ratio():
    from paddle_tpu.profiler.metrics import get_registry
    reg = get_registry()
    a, b, c = (reg.counter(f"perfbenchtest/{k}") for k in "abc")
    for m, v in ((a, 30), (b, 6), (c, 4)):
        m.set(v)
    one = {"num": "perfbenchtest/a", "den": "perfbenchtest/c"}
    two = {"num": ["perfbenchtest/a", "perfbenchtest/b"],
           "den": "perfbenchtest/c"}
    assert spans.read_registry_ratio(one, {}) == 7.5
    assert spans.read_registry_ratio(two, {}) == 6.0
    c.set(0)
    assert spans.read_registry_ratio(two, {}) is None   # no call yet
    missing = {"num": ["perfbenchtest/a", "perfbenchtest/nope"],
               "den": "perfbenchtest/a"}
    assert spans.read_registry_ratio(missing, {}) is None


def test_fresh_outputs_reads_the_to_static_counters():
    import paddle_tpu as paddle
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    from paddle_tpu.profiler.metrics import get_registry
    for n in ("compiled_calls", "outputs", "donated_inputs"):
        get_registry().counter("jit/" + n).set(0)   # this process's past
    for _ in range(4):
        step(x)
    graph = next(iter(next(iter(step._graphs.values())).by_key.values()))
    # the loss and every state buffer the step reassigns, none donated
    assert read("step.fresh_outputs.train", {}) == \
        1 + len(graph.pure_fn._holder["changed"])


def test_fresh_outputs_is_a_mean_over_every_compiled_function():
    """The counters are the process's, not a function's nor a window's:
    with a second compiled function the metric reads the blend, weighted
    by calls (the file's ``what`` says so)."""
    import paddle_tpu as paddle
    from paddle_tpu.profiler.metrics import get_registry

    @paddle.jit.to_static
    def one(x):
        return x * 2.0

    @paddle.jit.to_static
    def three(x):
        return x + 1.0, x - 1.0, x * x

    x = paddle.to_tensor(np.ones((4,), "float32"))
    one(x), three(x)                             # discovery: not counted
    for n in ("compiled_calls", "outputs", "donated_inputs"):
        get_registry().counter("jit/" + n).set(0)
    for _ in range(3):
        one(x)
    assert read("step.fresh_outputs.train", {}) == 1.0
    three(x)
    assert read("step.fresh_outputs.train", {}) == (3 * 1 + 3) / 4


def test_prefill_fill_is_read_by_the_gauge_reader():
    win = type("W", (), {"gauges": {"prefill_fill": 0.0525}})()
    assert read("step.prefill_fill.batch",
                {"kind": "serve", "win": win}) == pytest.approx(5.25)
    old = type("W", (), {"gauges": {"slot_occupancy": 0.9}})()
    assert read("step.prefill_fill.batch",
                {"kind": "serve", "win": old}) is None


# ---- the files' regexes against what the program really writes -------------

def _capture(tmp_path, body):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return xplane.load(xplane.find_xplane(str(tmp_path)))


def _with_device(tr):
    """The CPU trace has no device plane: lay a device that is busy for
    the middle half of every bench step span over its host events."""
    steps = xplane.step_spans(tr.host)
    tr.device_ops = {0: [Ev("op", e.start + (e.end - e.start) / 4,
                            e.end - (e.end - e.start) / 4) for e in steps]}
    window = (steps[0].start, steps[-1].end)
    return {"trace": tr, "reduced": {"window": window, "steps": len(steps)}}


def test_serving_files_match_a_real_engines_spans(tmp_path):
    import jax
    from conftest import load_cfg
    from perfbench.harness import model as model_mod, serve
    cfg = load_cfg("tiny-qwen2.json")
    model, _ = model_mod.build(cfg, 3, lambda *a, **k: None)
    model.eval()
    eng = serve.build_engine(cfg, model)
    rng = np.random.default_rng(0)
    vocab = cfg["sizes"]["vocab_size"]

    def feed(n):
        for _ in range(n):
            eng.add_request(rng.integers(0, vocab, 9).astype(np.int32), 6)

    feed(2)
    while eng.has_work():
        eng.step()

    def body():
        feed(3)
        while eng.has_work():
            with jax.profiler.TraceAnnotation("bench/engine.step"):
                eng.step()

    c = _with_device(_capture(tmp_path, body))
    parts = {n: read(n, c) for n in SERVE}
    assert all(v is not None for v in parts.values()), parts
    t0, t1 = c["reduced"]["window"]
    idle = sum(b - a for a, b in xplane.gaps(c["trace"].device_ops[0],
                                             t0, t1))
    assert sum(parts.values()) == pytest.approx(
        1e3 * idle / c["reduced"]["steps"], rel=1e-9)
    # half of every turn is idle by construction, and the turn is the
    # program's: the three named classes hold nearly all of it
    assert parts["pump.idle_ms_other.batch"] < 0.2 * sum(parts.values())
    # the reducer, unchanged, now names a program span under the harness's
    names = {xplane.host_activity(c["trace"].host, (a + b) / 2)
             for a, b in xplane.gaps(c["trace"].device_ops[0], t0, t1)}
    assert any(n.startswith("bench/engine.step>") for n in names), names


def test_training_files_match_a_real_to_static_steps_spans(tmp_path):
    import jax
    import paddle_tpu as paddle
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    step(x), step(x)

    def body():
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/train.step"):
                float(step(x))

    c = _with_device(_capture(tmp_path, body))
    ex, other = read(TRAIN[0], c), read(TRAIN[1], c)
    assert ex is not None and other is not None and ex > 0 and other > 0
    t0, t1 = c["reduced"]["window"]
    idle = sum(b - a for a, b in xplane.gaps(c["trace"].device_ops[0],
                                             t0, t1))
    assert ex + other == pytest.approx(1e3 * idle / 3, rel=1e-9)


def test_every_new_metric_has_its_file_and_its_entry(bench):
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in SERVE + TRAIN + ["step.prefill_fill.batch",
                                 "step.fresh_outputs.train"]:
        assert name in per, name
        path = os.path.join(spec.HERE, "metrics", name + ".json")
        with open(path) as f:
            assert "reader" in json.load(f)
        want = "program_counter" if name in (
            "step.prefill_fill.batch", "step.fresh_outputs.train") \
            else "program_span"
        assert per[name]["source"] == want
