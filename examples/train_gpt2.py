"""BASELINE config 1: GPT-2-small LM training, single device, CPU-runnable.

Trains on a synthetic in-memory corpus (zero-egress environment: no
downloads); the oracle is a healthy LM loss curve — fast early descent from
ln(vocab) — plus checkpoint save/resume continuity. Use --tiny for CI-speed.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.models import GPT2Config, GPT2ForCausalLM


def synthetic_corpus(vocab, n_tokens, seed=0):
    """Markov-ish synthetic text so the LM has learnable structure."""
    rng = np.random.RandomState(seed)
    trans = rng.dirichlet(np.ones(vocab) * 0.05, size=vocab)
    out = np.empty(n_tokens, np.int64)
    tok = 0
    for i in range(n_tokens):
        tok = rng.choice(vocab, p=trans[tok])
        out[i] = tok
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--compile", action=argparse.BooleanOptionalAction,
                    default=True)
    args = ap.parse_args()
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()

    cfg = GPT2Config.tiny() if args.tiny else GPT2Config.small()
    base_lr, warmup = 3e-4, 20
    if args.tiny:
        args.steps = min(args.steps, 120)
        base_lr, warmup = 2e-3, 5
    paddle.seed(0)
    model = GPT2ForCausalLM(cfg)
    n_params = sum(p.size for p in model.parameters())
    print(f"GPT-2 {n_params/1e6:.1f}M params, vocab {cfg.vocab_size}")

    corpus = synthetic_corpus(min(cfg.vocab_size, 512),
                              args.batch * args.seq * 50)
    sched = paddle.optimizer.lr.LinearWarmup(base_lr, warmup_steps=warmup,
                                             start_lr=0.0, end_lr=base_lr)
    opt = paddle.optimizer.AdamW(
        learning_rate=sched, parameters=model.parameters(),
        weight_decay=0.01,
        grad_clip=nn.ClipGradByGlobalNorm(1.0))

    def sample_batch(step):
        # the model shifts labels internally, so feed exactly seq tokens
        # (seq may equal max_position_embeddings)
        rng = np.random.RandomState(step)
        idx = rng.randint(0, corpus.size - args.seq, args.batch)
        return paddle.to_tensor(
            np.stack([corpus[i:i + args.seq] for i in idx]))

    def train_step(ids):
        _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    if args.compile:
        train_step = paddle.jit.to_static(train_step)

    losses = []
    t0 = time.perf_counter()
    for step in range(args.steps):
        loss = train_step(sample_batch(step))
        sched.step()
        losses.append(float(loss.item()))
        if step % 10 == 0:
            print(f"step {step:4d}  loss {losses[-1]:.4f}  "
                  f"lr {opt.get_lr():.2e}")
    dt = time.perf_counter() - t0
    print(f"{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")

    # checkpoint round trip
    import tempfile
    ckpt = tempfile.mkdtemp(prefix="gpt2_ckpt_")
    paddle.save(model.state_dict(), f"{ckpt}/model.pdparams")
    paddle.save(opt.state_dict(), f"{ckpt}/opt.pdopt")
    model.set_state_dict(paddle.load(f"{ckpt}/model.pdparams"))
    opt.set_state_dict(paddle.load(f"{ckpt}/opt.pdopt"))
    loss2 = float(train_step(sample_batch(0)).item())
    print(f"resumed step loss {loss2:.4f}")

    start = np.mean(losses[:5])
    end = np.mean(losses[-5:])
    assert end < start - 0.15, f"loss did not drop: {start} -> {end}"
    print("TRAIN OK")


if __name__ == "__main__":
    main()
