"""The unified step computes the prompt rows a turn holds: a loop over
groups of prefilling slots inside the ONE step program, sixteen decode
micro-steps, and a head that reads one row per slot. Float32 on the CPU,
with the module constant patched small so that a 4-slot engine runs
several groups and a ragged last group; the oracle is per-stream greedy
parity with dense ``generate``."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference import serving
from paddle_tpu.models import (GPT2Config, GPT2ForCausalLM, LlamaConfig,
                               LlamaForCausalLM, NemotronHConfig,
                               NemotronHForCausalLM, Qwen2Config,
                               Qwen2ForCausalLM)

CHUNK, SLOTS, GROUP = 8, 4, 3


def _model(kind, layers=2):
    cfg, cls = {"llama": (LlamaConfig, LlamaForCausalLM),
                "qwen2": (Qwen2Config, Qwen2ForCausalLM),
                "gpt2": (GPT2Config, GPT2ForCausalLM),
                "nemotron_h": (NemotronHConfig, NemotronHForCausalLM)}[kind]
    cfg = cfg.tiny()
    if kind != "nemotron_h":
        cfg.num_hidden_layers = layers
    if kind in ("llama", "qwen2"):
        cfg.tensor_parallel = False
        cfg.scan_layers = False
    paddle.seed(0)
    model = cls(cfg)
    model.eval()
    return model, cfg


def _ref_greedy(model, prompt, n_new):
    ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
    out, _ = model.generate(ids, max_new_tokens=n_new,
                            decode_strategy="greedy_search",
                            eos_token_id=None, pad_token_id=0)
    return np.asarray(out.numpy())[0].tolist()


def _engine(model, monkeypatch, group=GROUP, slots=SLOTS, **kw):
    monkeypatch.setattr(serving, "PREFILL_GROUP_POSITIONS", group * CHUNK)
    eng = ContinuousBatchingEngine(
        model, num_slots=slots, page_size=8, max_len=64,
        prefill_chunk=CHUNK, decode_chunk=4, greedy=True, audit=True, **kw)
    assert eng._group == group
    return eng


def _pump(eng):
    """Step to the end; per turn the groups its step program ran (read
    off the positions counter) and the requests it finished."""
    groups, done = [], {}
    while eng.has_work():
        before = eng.gauges()["prefill_positions"]
        for r in eng.step():
            done[r.request_id] = r
        delta = eng.gauges()["prefill_positions"] - before
        assert delta % (eng._group * CHUNK) == 0
        groups.append(delta // (eng._group * CHUNK))
    return groups, done


@pytest.mark.parametrize("kind", ["llama", "qwen2", "gpt2"])
def test_streams_equal_dense_generate_across_groups(kind, monkeypatch):
    """Seven requests on four slots in groups of three: the first turn
    prefills four slots (two groups, the second ragged), prompts span up
    to four chunks, slots are taken over in the turn after they drain,
    and turns without a prompt run the loop zero times."""
    model, cfg = _model(kind)
    rng = np.random.RandomState(3)
    shapes = [(5, 6), (19, 9), (8, 3), (27, 12), (3, 7), (12, 5), (16, 4)]
    prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L, _ in shapes]
    eng = _engine(model, monkeypatch)
    rids = [eng.add_request(p, n) for p, (_, n) in zip(prompts, shapes)]
    groups, done = _pump(eng)
    for rid, p, (_, n) in zip(rids, prompts, shapes):
        assert done[rid].error is None
        assert done[rid].tokens == _ref_greedy(model, p, n), rid
    assert groups[0] == 2 and {0, 1, 2} <= set(groups)
    g = eng.gauges()
    assert g["prefill_tokens"] == sum(L for L, _ in shapes)
    assert g["compiled_programs"] == 1


def test_a_slot_is_reused_in_the_turn_after_it_drains(monkeypatch):
    """One slot, three requests: each takes the slot over the turn after
    its predecessor drained, starting its own stream from the loop."""
    model, cfg = _model("llama", layers=1)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (9, 4, 13)]
    eng = _engine(model, monkeypatch, group=1, slots=1)
    rids = [eng.add_request(p, 5) for p in prompts]
    groups, done = _pump(eng)
    for rid, p in zip(rids, prompts):
        assert done[rid].tokens == _ref_greedy(model, p, 5)
    assert set(groups) == {0, 1}


@pytest.mark.parametrize("kind", ["llama", "qwen2", "gpt2", "nemotron_h"])
def test_logits_at_reads_the_rows_the_full_head_computes(kind):
    """``forward(..., logits_at=rows)`` is ``forward(...)[:, rows]``: the
    hidden states are gathered before the final norm and the head."""
    model, cfg = _model(kind)
    eng = ContinuousBatchingEngine(model, num_slots=3, page_size=8,
                                   max_len=32, prefill_chunk=CHUNK)
    mp = eng.pages_per_slot
    tbl = 1 + np.arange(3 * mp, dtype=np.int32).reshape(3, mp)
    lengths = np.asarray([5, 8, 1], np.int32)
    ids = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (3, CHUNK)).astype(np.int32)
    at = lengths - 1

    def run(**kw):
        with paddle.no_grad():
            logits, _ = model(
                Tensor(jax.numpy.asarray(ids)), caches=list(eng.pools),
                pos=Tensor(jax.numpy.zeros((3, 1), jax.numpy.int32)),
                tables=(Tensor(jax.numpy.asarray(tbl)),
                        Tensor(jax.numpy.asarray(lengths))), **kw)
        return np.asarray(logits._data)

    full = run()
    rows = run(logits_at=Tensor(jax.numpy.asarray(at)))
    assert full.shape == (3, CHUNK, cfg.vocab_size)
    assert rows.shape == (3, 1, cfg.vocab_size)
    np.testing.assert_allclose(rows[:, 0], full[np.arange(3), at],
                               atol=1e-5)


class _Built:
    """Programs jax builds (compiled or fetched from the persistent
    cache: both fire the backend-compile event)."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def test_one_step_program_whatever_the_row_count(monkeypatch):
    """The group loop's trip count is data: after the discovery turn and
    the compiled one, turns with 0, 1, 3 and 4 prefilling slots (0, 1
    and 2 groups of two) build nothing, and the engine holds ONE unified
    program."""
    model, cfg = _model("llama", layers=1)
    rng = np.random.RandomState(6)
    eng = _engine(model, monkeypatch, group=2)

    def serve(lengths):
        for L in lengths:
            eng.add_request(
                rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32), 6)
        return _pump(eng)[0]

    serve((5, 7))               # discovery turn, compiled turn, admission
    built = _Built()
    seen = set()
    for lengths in ((6,), (4, 11, 3), (9, 5, 12, 7)):
        seen |= set(serve(lengths))
    assert seen == {0, 1, 2}
    assert built.n == 0
    fn = eng._unified_fn
    assert fn.n_eager_runs == 1 and len(fn.program_texts()) == 1
    assert eng.gauges()["compiled_programs"] == 1
    (sig,) = eng._compiled
    assert sig == ("unified", CHUNK, 4)
