"""End-to-end LLM serving flow: build a Llama, export it with
``paddle.jit.save``, load it into the inference engine
(``Config``/``create_predictor``), and run batched KV-cache generation —
greedy and sampling — through the fused device-side decode loop.

The model size is an argument, never a guess from the device:
``--size tiny`` (default; CPU-runnable in seconds) or ``--size 1b``
(``LlamaConfig.llama_1b`` in bf16 — meant for a chip).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

from paddle_tpu.framework.compile_cache import ensure_compile_cache

ap = argparse.ArgumentParser(description=__doc__)
ap.add_argument("--size", choices=("tiny", "1b"), default="tiny")
BIG = ap.parse_args().size == "1b"
ensure_compile_cache()

cfg = LlamaConfig.llama_1b() if BIG else LlamaConfig.tiny()
cfg.tensor_parallel = False
cfg.scan_layers = False

paddle.seed(0)
model = LlamaForCausalLM(cfg)
if BIG:
    model.to(dtype="bfloat16")
model.eval()

batch, prompt_len, n_new = (8, 128, 64) if BIG else (2, 8, 12)
prompt = paddle.to_tensor(np.random.RandomState(0).randint(
    0, cfg.vocab_size, (batch, prompt_len)).astype(np.int64))

# ---- 1. generation: greedy (deterministic) and sampling ------------------
print("== generate ==")
t0 = time.time()
ids_greedy, scores = model.generate(prompt, max_new_tokens=n_new,
                                    decode_strategy="greedy_search",
                                    eos_token_id=None, pad_token_id=0)
print(f"greedy [{batch}x{n_new}] in {time.time() - t0:.2f}s "
      f"(first compile included); scores {scores.numpy().round(3).tolist()}")
ids_sampled, _ = model.generate(prompt, max_new_tokens=n_new,
                                decode_strategy="sampling", top_p=0.9,
                                temperature=0.8, seed=7,
                                eos_token_id=None, pad_token_id=0)
assert list(ids_greedy.shape) == [batch, n_new]
assert list(ids_sampled.shape) == [batch, n_new]
print("sampled row 0:", ids_sampled.numpy()[0][:10].tolist(), "...")

# ---- 2. export for the inference engine ----------------------------------
print("== export / predictor ==")
export_dir = os.path.join(os.path.dirname(__file__) or ".",
                          "_llama_export")
from paddle_tpu.jit import save as jit_save
from paddle_tpu.static import InputSpec

jit_save(model, os.path.join(export_dir, "llama"),
         input_spec=[InputSpec([None, prompt_len], "int64", "input_ids")])

from paddle_tpu.inference import Config, create_predictor

config = Config(os.path.join(export_dir, "llama.pdmodel"),
                os.path.join(export_dir, "llama.pdiparams"))
predictor = create_predictor(config)
in_names = predictor.get_input_names()
h = predictor.get_input_handle(in_names[0])
h.copy_from_cpu(np.asarray(prompt.numpy()))
predictor.run()
out = predictor.get_output_handle(predictor.get_output_names()[0])
logits = out.copy_to_cpu()
print("predictor logits:", logits.shape)
assert logits.shape[:2] == (batch, prompt_len)

# exported predictor and the live model agree
with paddle.no_grad():
    ref = model(prompt).numpy()
np.testing.assert_allclose(logits, ref, rtol=2e-2, atol=2e-2)
print("predictor == live model OK")

# ---- 3. continuous batching: mixed-length streams over paged KV ----------
print("== continuous batching ==")
from paddle_tpu.inference import ContinuousBatchingEngine

if BIG:
    eng_kw = dict(num_slots=4, page_size=16, max_len=prompt_len + 128,
                  decode_chunk=16, prefill_chunk=128)
    req_specs = [(prompt_len, 64), (prompt_len // 2, 48),
                 (prompt_len // 4, 96), (prompt_len, 32),
                 (prompt_len // 2, 64), (prompt_len // 4, 80)]
else:
    eng_kw = dict(num_slots=2, page_size=8, max_len=48,
                  decode_chunk=4, prefill_chunk=16)
    req_specs = [(6, 8), (12, 5), (9, 10), (4, 6), (14, 7)]

engine = ContinuousBatchingEngine(model, greedy=True, **eng_kw)
rng = np.random.RandomState(3)
reqs = []
for plen, n in req_specs:
    p = rng.randint(0, cfg.vocab_size, (plen,)).astype(np.int32)
    reqs.append((p, n, engine.add_request(p, n)))
t0 = time.time()
done = engine.run()
dt = time.time() - t0
total_toks = sum(len(r.tokens) for r in done)
g = engine.gauges()
print(f"served {len(done)} mixed-length streams "
      f"({[s for s, _, _ in [(p.size, n, i) for p, n, i in reqs]]}-token "
      f"prompts) -> {total_toks} tokens in {dt:.2f}s "
      f"(compile included)")
print(f"ttft p50 {g['ttft_ms_p50']:.1f}ms / p99 {g['ttft_ms_p99']:.1f}ms, "
      f"itl p50 {g['itl_ms_p50']:.2f}ms, "
      f"{g['prefill_waves']} batched prefill waves, "
      f"{g['compiled_programs']} compiled programs")
# spot-check one stream against the dense-cache generate path
p0, n0, id0 = reqs[0]
ref_ids, _ = model.generate(
    paddle.to_tensor(p0.reshape(1, -1).astype(np.int64)),
    max_new_tokens=n0, decode_strategy="greedy_search",
    eos_token_id=None, pad_token_id=0)
got = next(r for r in done if r.request_id == id0).tokens
assert got == np.asarray(ref_ids.numpy())[0].tolist(), "CB != generate"
print("continuous batching == dense generate OK")
print("ALL OK")
