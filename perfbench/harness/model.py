"""Building the program's model and giving it the seed's weights."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from . import spec, weights


def jnp_dtype(name):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def set_seed_weights(cfg, named, specs, seed):
    """Every parameter replaced by the seed's, in one jitted call; the
    values that were there donate their buffers."""
    new = weights.make_all(specs, seed, cfg["init_std"],
                           jnp_dtype(cfg["dtype"]),
                           donate=[p._data for _, p in named])
    for (_, p), arr in zip(named, new):
        p.set_data(arr)
    jax.block_until_ready(new)


def build(cfg, seed, say):
    """The program's model, as a user builds it, then every parameter
    replaced by the seed's (one jitted call; the program's initial values
    donate their buffers). Returns (model, specs)."""
    import paddle_tpu as paddle

    t0 = time.perf_counter()
    paddle.seed(0)
    model = spec.build_model(cfg)
    dtype = jnp_dtype(cfg["dtype"])
    if dtype != jnp.float32:
        model.to(dtype=cfg["dtype"])
    named = list(model.named_parameters())
    ref = spec.reference_module(cfg)
    specs = ref.param_specs(cfg["sizes"])
    got = [(n, tuple(p.shape)) for n, p in named]
    want = [(n, tuple(s)) for n, s, _ in specs]
    if got != want:
        diff = [(g, w) for g, w in zip(got, want) if g != w][:5]
        raise SystemExit(f"perfbench: the program's parameters are not the "
                         f"reference's: {len(got)} vs {len(want)}, first "
                         f"differences {diff}")
    t1 = time.perf_counter()
    mem = lambda: round(((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) / 1e9, 2)
    peak_built = mem()
    set_seed_weights(cfg, named, specs, seed)
    n_params = sum(int(p.size) for _, p in named)
    say("model", params=f"{n_params / 1e9:.3f}B", dtype=cfg["dtype"],
        construct_s=round(t1 - t0, 1), peak_gb_after_construct=peak_built,
        peak_gb_after_seed_weights=mem(),
        seed_weights_s=round(time.perf_counter() - t1, 1))
    return model, specs
