"""Qwen2-MoE training — dropless dispatch, expert parallelism, and the
MoE x pipeline composition, end to end.

Three modes in one script (pick with --mode):

- "single":  one device, DROPLESS routed experts over the Pallas
             grouped matmul (no capacity, no token drops) — the
             single-chip bench configuration (bench.py moe section).
- "ep":      expert parallelism over the 'expert' mesh axis — the
             all-to-all dispatch/combine (capacity form, per-device
             quotas bound the a2a payload). Run under
             XLA_FLAGS=--xla_force_host_platform_device_count=8
             JAX_PLATFORMS=cpu for a virtual mesh.
- "ep_pp":   ep2 x pp2 with the explicit 1F1B tick engine — the
             reference's MoE production schedule (SURVEY.md §3.4),
             expert banks sharded THROUGH the pipeline's manual region.
"""

import argparse
import dataclasses
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (Qwen2MoeConfig, Qwen2MoeForCausalLM,
                               Qwen2MoeForCausalLMPipe)


def make_cfg(dropless):
    return dataclasses.replace(
        Qwen2MoeConfig.tiny(), num_hidden_layers=4,
        capacity_factor=2.0, router_aux_loss_coef=0.0,
        moe_dropless=dropless, scan_layers=False)


def _train_loop(cfg, steps, suffix=""):
    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 32)).astype(np.int64))

    @paddle.jit.to_static
    def step(t):
        _, loss = model(t, labels=t)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for i in range(steps):
        print(f"step {i}: loss {float(step(ids).item()):.4f}{suffix}")


def run_single(steps):
    _train_loop(make_cfg(dropless=True), steps)


def run_ep(steps):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": -1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1, "ep_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    # EP runs the capacity all-to-all (per-device quotas bound the a2a)
    _train_loop(make_cfg(dropless=False), steps, "  (ep4 all-to-all)")


def run_ep_pp(steps):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 2, "sharding_degree": 1,
                               "sep_degree": 1, "ep_degree": 2}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "schedule_mode": "1F1B"}
    fleet.init(is_collective=True, strategy=strategy)
    cfg = make_cfg(dropless=False)
    paddle.seed(0)
    model = Qwen2MoeForCausalLMPipe(cfg)
    engine = fleet.fleet.distributed_model(model)
    opt = fleet.fleet.distributed_optimizer(
        paddle.optimizer.AdamW(1e-3, parameters=model.parameters()))
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 32)).astype(np.int64))
    for i in range(steps):
        loss = engine.train_batch((ids, ids), opt)
        print(f"step {i}: loss {float(loss.item()):.4f}  "
              f"(ep2 x pp2, explicit 1F1B)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="single",
                    choices=["single", "ep", "ep_pp"])
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    {"single": run_single, "ep": run_ep,
     "ep_pp": run_ep_pp}[args.mode](args.steps)
