"""Serving-parity CI gate: the engine's greedy token streams are EXACTLY
dense ``model.generate``'s on a mixed small workload — for every served
family that has a tiny preset, under both pumps (``run()`` and
``add_request`` + ``step()``), without and with a per-request eos — and
steady state is ONE compiled program. Wired into ``tools/run_gates.py``
as the ``serving_parity`` gate (fast tier — tiny models keep it inside
the budget tool's tripwire)."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import (GPT2Config, GPT2ForCausalLM, LlamaConfig,
                               LlamaForCausalLM, NemotronHConfig,
                               NemotronHForCausalLM, Qwen2Config,
                               Qwen2ForCausalLM)

_FAMILIES = {
    "llama": (LlamaConfig, LlamaForCausalLM),
    "qwen2": (Qwen2Config, Qwen2ForCausalLM),
    "gpt2": (GPT2Config, GPT2ForCausalLM),
    "nemotron_h": (NemotronHConfig, NemotronHForCausalLM),
}

# mixed workload: multi-chunk prompt, mid-stream drain + re-admit,
# a one-token request
_SPECS = [(5, 6), (11, 3), (19, 5), (4, 1), (8, 4)]


def _tiny_model(family):
    config, model = _FAMILIES[family]
    cfg = config.tiny()
    for k in ("tensor_parallel", "scan_layers"):
        if hasattr(cfg, k):
            setattr(cfg, k, False)
    paddle.seed(0)
    m = model(cfg)
    m.eval()
    return m, cfg


def _engine(model):
    return ContinuousBatchingEngine(
        model, num_slots=2, page_size=8, max_len=48, decode_chunk=4,
        prefill_chunk=16, greedy=True)


def _prompts(cfg):
    rng = np.random.RandomState(21)
    return [rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32)
            for plen, _ in _SPECS]


def _dense(model, prompts, eos_for=None):
    """The oracle: each request alone through the dense-cache greedy
    ``generate``, cut after its eos where it has one."""
    out = []
    for i, (prompt, (_, n)) in enumerate(zip(prompts, _SPECS)):
        ids = paddle.to_tensor(prompt.reshape(1, -1).astype(np.int64))
        toks, _ = model.generate(ids, max_new_tokens=n,
                                 decode_strategy="greedy_search",
                                 eos_token_id=None, pad_token_id=0)
        toks = np.asarray(toks.numpy())[0].tolist()
        eos = eos_for.get(i) if eos_for else None
        if eos in toks:
            toks = toks[:toks.index(eos) + 1]
        out.append((toks, "eos" if toks[-1] == eos else "length"))
    return out


def _serve(eng, prompts, pump, eos_for=None):
    ids = [eng.add_request(
        p, n, eos_token_id=eos_for.get(i) if eos_for else None)
        for i, (p, (_, n)) in enumerate(zip(prompts, _SPECS))]
    if pump == "run":
        done = eng.run()
    else:
        done = []
        while eng.has_work():
            done.extend(eng.step())
    by_id = {r.request_id: r for r in done}
    return [(by_id[rid].tokens, by_id[rid].finish_reason) for rid in ids]


@pytest.mark.serving_parity
@pytest.mark.parametrize("pump", ["run", "step"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_engine_streams_equal_dense_generate(family, pump):
    """The gate: HOW the work is scheduled onto the step program —
    chunked prompts beside decoding slots, slots drained and re-admitted
    mid-flight, the successor dispatched before the harvest or after it
    — changes no token and no finish reason."""
    model, cfg = _tiny_model(family)
    prompts = _prompts(cfg)
    assert _serve(_engine(model), prompts, pump) == _dense(model, prompts)


@pytest.mark.serving_parity
@pytest.mark.parametrize("pump", ["run", "step"])
def test_engine_streams_equal_dense_generate_with_eos(pump):
    """Same gate with a stop the host cannot predict: a real eos token
    from the model's own continuation cuts request 0 mid-stream, inside
    a step's decode micro-steps."""
    model, cfg = _tiny_model("llama")
    prompts = _prompts(cfg)
    toks0 = _dense(model, prompts)[0][0]
    eos_for = {0: int(toks0[1])}
    want = _dense(model, prompts, eos_for)
    assert want[0][1] == "eos" and len(want[0][0]) < len(toks0)
    assert _serve(_engine(model), prompts, pump, eos_for) == want


@pytest.mark.serving_parity
def test_steady_state_is_one_compiled_program():
    """Compile-count half of the gate: every prompt length, every mix
    of prefilling and decoding slots and both pumps run the ONE step
    program."""
    model, cfg = _tiny_model("llama")
    eng = _engine(model)
    prompts = _prompts(cfg)
    _serve(eng, prompts, "run")
    _serve(eng, prompts[::-1], "step")
    g = eng.gauges()
    assert g["compiled_programs"] == 1, eng._compiled
    assert g["unified_steps"] == g["chunks_dispatched"] > 0
