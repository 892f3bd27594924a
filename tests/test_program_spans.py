"""Program spans on the profiler session's clock (ISSUE 25): the one span
primitive (``profiler.trace.trace_span`` — a ``TraceAnnotation`` always, a
chrome event too while the structured tracer is on), the engine's step
pump and the ``to_static`` call split where the work happens, the
prefill-fill and output-buffer counters, and stable Pallas kernel names.

The traces are captured on the CPU and read back with
``jax.profiler.ProfileData``: what is checked is nesting and counts, never
a time."""

import glob
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import trace
from paddle_tpu.profiler.metrics import get_registry

SLOTS, CHUNK = 2, 8


def _engine(**kw):
    cfg = LlamaConfig.tiny()
    cfg.tensor_parallel = False
    cfg.scan_layers = False
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    kw = {"num_slots": SLOTS, "page_size": 8, "max_len": 64,
          "decode_chunk": 4, "prefill_chunk": CHUNK, "greedy": True,
          "prefix_cache": False, **kw}
    return ContinuousBatchingEngine(model, **kw), cfg


def _prompts(cfg, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _pump(eng):
    turns = 0
    while eng.has_work():
        eng.step()
        turns += 1
    return turns


def _host_events(trace_dir, prefixes):
    """[(name, start_ns, end_ns, stats)] of the host plane's events whose
    name starts with one of ``prefixes``, by start."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _traced(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    return out


def _children(evs, parent, prefixes):
    """Events of ``evs`` inside ``parent`` whose name starts with one of
    ``prefixes`` — the parent itself left out."""
    _, a, b, _ = parent
    return [e for e in evs if e is not parent and e[0].startswith(prefixes)
            and a <= e[1] and e[2] <= b]


def _assert_disjoint(evs):
    for x, y in zip(evs, evs[1:]):
        assert x[2] <= y[1], (x[0], y[0])


# ---- the primitive ---------------------------------------------------------

def test_a_span_records_nothing_with_no_session_and_the_tracer_off():
    tr = trace.get_tracer()
    assert not tr.enabled
    before = len(tr.events)
    with trace.trace_span("unit/quiet", n=1) as sp:
        sp.set_args(m=2)
    assert len(tr.events) == before
    # and a private disabled tracer likewise
    off = trace.Tracer(enabled=False)
    with off.span("unit/quiet"):
        pass
    assert off.events == []


def test_an_enabled_tracer_records_the_chrome_event_it_always_did():
    tr = trace.Tracer(enabled=True)
    with tr.span("outer", cat="train", flops=10.0) as sp:
        with tr.span("inner"):
            pass
        sp.set_args(bytes=4.0)
    inner, outer = tr.events
    assert (outer.name, outer.ph, outer.cat, outer.depth) == \
        ("outer", "X", "train", 0)
    assert outer.args == {"flops": 10.0, "bytes": 4.0}
    assert (inner.name, inner.depth, inner.args) == ("inner", 1, {})
    assert outer.ts <= inner.ts
    assert inner.ts + inner.dur <= outer.ts + outer.dur
    ev = outer.to_chrome(pid=1)
    assert ev["ph"] == "X" and ev["dur"] == outer.dur


def test_one_span_lands_in_both_sinks(tmp_path):
    """Under a profiler session AND an enabled tracer the same span is a
    host-plane event with its args and a chrome event."""
    tr = trace.Tracer(enabled=True)

    def body():
        with tr.span("unit/both", seq=7) as sp:
            sp.set_args(appended=3)
        with profiler.RecordEvent("unit/record_event"):
            pass

    _traced(tmp_path, body)
    evs = _host_events(str(tmp_path), ("unit/",))
    assert [e[0] for e in evs] == ["unit/both", "unit/record_event"]
    assert evs[0][3] == {"seq": 7, "appended": 3}
    assert [(e.name, e.args) for e in tr.events] == \
        [("unit/both", {"seq": 7, "appended": 3})]


def test_trace_module_imports_without_jax():
    import subprocess
    import sys
    code = ("import sys, importlib.util as u\n"
            "spec = u.spec_from_file_location('t', sys.argv[1])\n"
            "m = u.module_from_spec(spec); sys.modules['t'] = m\n"
            "spec.loader.exec_module(m)\n"
            "assert 'jax' not in sys.modules, 'trace.py imported jax'\n")
    subprocess.run([sys.executable, "-c", code, trace.__file__],
                   check=True, timeout=60)


# ---- the serving turn ------------------------------------------------------

@pytest.fixture(scope="module")
def serving_trace(tmp_path_factory):
    """A tiny engine pumped by step() under a profiler session (its first
    turns, discovery and compile, run before the session starts)."""
    d = tmp_path_factory.mktemp("serving_trace")
    eng, cfg = _engine()
    for p in _prompts(cfg, (5, 11)):
        eng.add_request(p, 6)
    _pump(eng)                          # discovery turn, compile
    eng.reset_gauges()
    prompts = _prompts(cfg, (3, 13, 9), seed=1)

    def body():
        for p in prompts:
            eng.add_request(p, 7)
        return _pump(eng)

    turns = _traced(d, body)
    return _host_events(str(d), ("serving/", "to_static/")), turns, eng, \
        prompts


def test_one_serving_step_span_per_turn(serving_trace):
    evs, turns, eng, prompts = serving_trace
    steps = [e for e in evs if e[0] == "serving/step"]
    assert len(steps) == turns >= 3
    _assert_disjoint(steps)
    assert all("seq" in e[3] for e in steps)
    adds = [e for e in evs if e[0] == "serving/add_request"]
    assert [e[3]["prompt_len"] for e in adds] == [len(p) for p in prompts]


def test_a_turns_children_nest_and_do_not_overlap(serving_trace):
    evs, _, _, _ = serving_trace
    layers = ("serving/admit", "serving/dispatch", "serving/harvest",
              "serving/drain")
    dispatched = 0
    for step in (e for e in evs if e[0] == "serving/step"):
        kids = [e for e in _children(evs, step, ("serving/",))
                if e[0] in layers]
        names = [e[0] for e in kids]
        assert names[0] == "serving/admit" and names[-1] == "serving/drain"
        _assert_disjoint(kids)
        if "serving/dispatch" not in names:
            continue
        dispatched += 1
        assert names == list(layers)
        disp, harv = kids[1], kids[2]
        assert disp[3]["seq"] == harv[3]["seq"] == step[3]["seq"]
        assert {"active", "prefilling", "prefill_tokens",
                "prefill_groups", "chunk_len"} <= set(disp[3])
        assert "appended" in harv[3]
        inner = _children(evs, disp, ("serving/dispatch.",))
        assert [e[0] for e in inner] == ["serving/dispatch.stage",
                                         "serving/dispatch.launch"]
        _assert_disjoint(inner)
        calls = _children(evs, inner[1], ("to_static/call",))
        assert len(calls) == 1 and calls[0][3]["mode"] == "compiled"
        assert calls[0][3]["fn"] == "ustep"
        fetch = _children(evs, harv, ("serving/harvest.fetch",))
        assert len(fetch) == 1
    assert dispatched >= 3


def test_spans_outside_a_turn_are_only_add_request(serving_trace):
    evs, _, _, _ = serving_trace
    steps = [e for e in evs if e[0] == "serving/step"]
    for e in evs:
        if e[0] in ("serving/step", "serving/add_request"):
            continue
        assert any(s[1] <= e[1] and e[2] <= s[2] for s in steps), e[0]


def test_prefill_counters_count_tokens_and_positions(serving_trace):
    """Positions are what the step's group loop computed: groups x rows a
    group x chunk, summed over turns; a turn whose slots all decode runs
    no group and adds none."""
    evs, _, eng, prompts = serving_trace
    g = eng.gauges()
    assert g["prefill_tokens"] == sum(len(p) for p in prompts)
    turns = [e[3] for e in evs if e[0] == "serving/dispatch"]
    assert len(turns) == g["unified_steps"]
    rows = eng._group
    assert rows == SLOTS            # 1,024 positions a group > 2 x 8
    for t in turns:
        assert t["prefill_groups"] == -(-t["prefilling"] // rows)
    assert 0 in {t["prefill_groups"] for t in turns}
    assert g["prefill_positions"] == rows * CHUNK * sum(
        t["prefill_groups"] for t in turns)
    assert g["prefill_positions"] < SLOTS * CHUNK * g["unified_steps"]
    assert g["prefill_fill"] == pytest.approx(
        g["prefill_tokens"] / g["prefill_positions"])
    assert 0.0 < g["prefill_fill"] <= 1.0


@pytest.mark.parametrize("pump", ["step", "run"])
def test_run_seconds_accumulate_per_turn_whoever_pumps(pump):
    """tokens_per_s and obs_overhead_frac read 0 under step() pumping
    before this PR; run() must not count its turns twice."""
    import time
    eng, cfg = _engine()
    for p in _prompts(cfg, (5, 9)):
        eng.add_request(p, 5)
    t0 = time.perf_counter()
    if pump == "step":
        _pump(eng)
    else:
        eng.run()
    wall = time.perf_counter() - t0
    g = eng.gauges()
    secs = eng._stats["run_seconds"]
    assert 0.0 < secs <= wall
    assert g["tokens_per_s"] == pytest.approx(g["tokens_emitted"] / secs)
    assert g["tokens_per_s"] > 0.0
    eng.reset_gauges()
    assert eng.gauges()["prefill_positions"] == 0
    assert eng._stats["run_seconds"] == 0.0


def test_serving_step_donates_nothing():
    """The serving step programs read the model's weights and write no
    state (the KV pools are call ARGUMENTS), so to_static's donation
    rule leaves them as they were: no aliased or donated parameter in
    the unified step's program, and the counter stands still."""
    eng, cfg = _engine()
    donated = get_registry().counter("jit/donated_inputs")
    calls = get_registry().counter("jit/compiled_calls")
    for p in _prompts(cfg, (5, 9)):
        eng.add_request(p, 5)
    eng.step()                                   # discovery turn
    before = donated.value, calls.value
    _pump(eng)
    assert donated.value == before[0] and calls.value > before[1]
    texts = eng._unified_fn.program_texts()
    assert texts
    for text in texts:
        assert "tf.aliasing_output" not in text
        assert "jax.buffer_donor" not in text
    for entry in eng._unified_fn._graphs.values():
        for graph in entry.by_key.values():
            assert not graph.written and graph.read_only


# ---- the to_static call ----------------------------------------------------

def _train_step(guarded=False):
    """A plain ``to_static`` AdamW step, as a user writes it. ``guarded``
    puts a branch on a device scalar into it: a graph with a guard."""
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())

    def step(x, y):
        loss = ((net(x) - y) ** 2).mean()
        if guarded and loss > 0:
            loss = loss * 1.0
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.random.RandomState(0).rand(16, 8)
                         .astype("float32"))
    y = paddle.to_tensor(np.random.RandomState(1).rand(16, 4)
                         .astype("float32"))
    return paddle.jit.to_static(step), x, y


def _reads_state_only():
    paddle.seed(0)
    net = paddle.nn.Linear(8, 4)

    @paddle.jit.to_static
    def infer(x, y):
        out = net(x)
        return out, ((out - y) ** 2).mean()

    x = paddle.to_tensor(np.ones((16, 8), "float32"))
    y = paddle.to_tensor(np.ones((16, 4), "float32"))
    return infer, x, y


def test_to_static_call_spans(tmp_path):
    fn, x, y = _train_step()

    def body():
        return [float(fn(x, y)) for _ in range(4)]

    losses = _traced(tmp_path, body)
    assert losses[-1] < losses[0]
    evs = _host_events(str(tmp_path), ("to_static/",))
    calls = [e for e in evs if e[0] == "to_static/call"]
    assert len(calls) == 4
    _assert_disjoint(calls)
    assert [c[3]["mode"] for c in calls] == ["discover"] + ["compiled"] * 3
    assert all(c[3]["fn"] == "step" for c in calls)
    kids = [[e[0] for e in _children(evs, c, ("to_static/",))]
            for c in calls]
    assert kids[0] == ["to_static/bind", "to_static/discover"]
    for k, c in zip(kids[1:], calls[1:]):
        assert k == ["to_static/bind", "to_static/execute",
                     "to_static/commit"]
        _assert_disjoint(_children(evs, c, ("to_static/",)))
    assert sum(e[0] == "to_static/execute" for e in evs) == 3


@pytest.mark.parametrize("case", ["train_step", "reads_state_only",
                                  "guarded"])
def test_jit_counters_outputs_and_donated_inputs(case):
    """The rule: a guard-free graph donates exactly the state the step
    reassigns, and returns that and the function's outputs; state that is
    only read is neither donated nor returned; a guarded graph donates
    nothing (a mispredicted run must be discardable)."""
    reg = get_registry()
    names = ("jit/compiled_calls", "jit/outputs", "jit/donated_inputs")

    def read():
        return [reg.counter(n).value for n in names]

    fn, x, y = {"train_step": _train_step, "reads_state_only":
                _reads_state_only,
                "guarded": lambda: _train_step(guarded=True)}[case]()
    base = read()
    fn(x, y)                                    # discovery: eager
    assert read() == base
    per_call = []
    for _ in range(3):
        before = read()
        fn(x, y)
        per_call.append([a - b for a, b in zip(read(), before)])
    assert all(p == per_call[0] for p in per_call)
    calls, outs, donated = per_call[0]
    assert calls == 1
    graph = next(iter(next(iter(fn._graphs.values())).by_key.values()))
    aliased = fn.program_texts()[0].count("tf.aliasing_output")
    if case == "reads_state_only":
        assert not graph.written and len(graph.read_only) == 2
        assert (outs, donated, aliased) == (2, 0, 0)   # its two results
        return
    # weight and bias x (value, two moments, two beta powers) + the
    # optimizer's two device scalars, as in the benchmark's 148 x 5 + 2
    n = 2 * 5 + 2
    assert len(graph.written) == n and not graph.read_only
    assert bool(graph.guard_log) == (case == "guarded")
    if case == "guarded":
        assert (outs, donated, aliased) == (1 + n, 0, 0)
    else:
        assert donated == len(graph.written)
        assert outs - donated == 1                      # the loss
        assert aliased == donated


def test_the_program_is_named_after_the_user_function():
    fn, x, y = _train_step()
    fn(x, y)
    fn(x, y)
    (text,) = fn.program_texts()
    assert "jit_to_static_step" in text


# ---- kernel names ----------------------------------------------------------

def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def test_flash_attention_kernels_are_named():
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    names = set(_pallas_names(jaxpr.jaxpr, []))
    assert {"flash_attention_fwd", "flash_attention_dkv",
            "flash_attention_dq"} <= names


def test_every_pallas_call_in_the_tree_has_a_name():
    import ast
    root = os.path.join(os.path.dirname(paddle.__file__), "ops", "pallas")
    unnamed = []
    for f in sorted(os.listdir(root)):
        if not f.endswith(".py"):
            continue
        tree = ast.parse(open(os.path.join(root, f)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call" \
                    and not any(k.arg == "name" for k in node.keywords):
                unnamed.append(f"{f}:{node.lineno}")
    assert not unnamed, unnamed
