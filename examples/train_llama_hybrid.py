"""Hybrid-parallel Llama training example.

Two phases over an 8-device mesh, each ONE compiled step (SURVEY.md §2.3):

1. TP x SP x ring-context x ZeRO-sharding x DP on the monolithic
   LlamaForCausalLM (GSPMD lays out every axis).
2. The 4D hybrid WITH pipeline: dp x sharding x mp x pp on
   LlamaForCausalLMPipe — stage weights stacked over 'pipe' (ppermute
   schedule inside a lax.scan), TP linears sharded over 'model',
   optimizer state ZeRO-sharded over 'sharding' (BASELINE config 4's
   workload shape).

Defaults to an 8-device virtual CPU mesh (pass PADDLE_TPU_EXAMPLE_REAL=1
to use whatever devices jax exposes).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

if not os.environ.get("PADDLE_TPU_EXAMPLE_REAL"):
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaForCausalLMPipe)


def _reset_fleet():
    fleet.fleet._hcg = None
    fleet.fleet._topology = None
    fleet.fleet._is_initialized = False


def train_gspmd_hybrid():
    n = len(jax.devices())
    mp = 2 if n % 2 == 0 else 1
    sep = 2 if n % 4 == 0 else 1
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": -1, "mp_degree": mp,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": sep, "ep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    mesh = hcg.global_mesh
    dp = hcg.get_data_parallel_world_size()
    print(f"mesh: dp={dp} mp={mp} sep={sep} over {n} devices")

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=128, max_position_embeddings=64,
                      rope_theta=10000.0, tensor_parallel=mp > 1,
                      sequence_parallel=mp > 1,
                      sep_parallel="ring" if sep > 1 else None)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    opt = fleet.distributed_optimizer(opt)

    batch = 4 * dp
    rng = np.random.RandomState(0)

    @paddle.jit.to_static
    def train_step(ids):
        _, loss = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for step in range(5):
        ids_np = rng.randint(0, cfg.vocab_size, (batch, 32)).astype("int64")
        ids = jax.device_put(
            jnp.asarray(ids_np),
            NamedSharding(mesh, PartitionSpec(("data", "sharding"), "sep")))
        loss = train_step(paddle.Tensor(ids))
        print(f"step {step}: loss {float(loss.item()):.4f}")
    print("hybrid training OK")
    _reset_fleet()


def train_pipeline_hybrid():
    """Phase 2: dp x sharding x mp x pp in ONE compiled pipeline program."""
    n = len(jax.devices())
    if n % 8:
        print(f"pipeline hybrid: skipped ({n} devices, need a multiple "
              f"of 8)")
        return
    pp, mp, sh = 2, 2, 2
    dp = n // (pp * mp * sh)
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": mp,
                               "pp_degree": pp, "sharding_degree": sh,
                               "sep_degree": 1, "ep_degree": 1}
    strategy.pipeline_configs = {"accumulate_steps": 2,
                                 "schedule_mode": "FThenB"}
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()
    mesh = hcg.global_mesh
    print(f"mesh: dp={dp} sharding={sh} mp={mp} pp={pp} over {n} devices")

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, num_hidden_layers=4,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=128, max_position_embeddings=64,
                      rope_theta=10000.0, tensor_parallel=mp > 1)
    paddle.seed(0)
    model = LlamaForCausalLMPipe(cfg)
    engine = fleet.fleet.distributed_model(model)
    opt = fleet.fleet.distributed_optimizer(
        paddle.optimizer.AdamW(1e-3, parameters=model.parameters()))

    batch = 4 * dp * sh
    rng = np.random.RandomState(0)
    for step in range(5):
        ids_np = rng.randint(0, cfg.vocab_size, (batch, 32)).astype("int64")
        ids = jax.device_put(
            jnp.asarray(ids_np),
            NamedSharding(mesh, PartitionSpec(("data", "sharding"))))
        ids_p = paddle.Tensor(ids)
        loss = engine.train_batch((ids_p, ids_p), opt)
        print(f"step {step}: loss {float(loss.item()):.4f}")
    print("pipeline hybrid training OK")
    _reset_fleet()


def main():
    from paddle_tpu.framework.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    train_gspmd_hybrid()
    train_pipeline_hybrid()


if __name__ == "__main__":
    main()
