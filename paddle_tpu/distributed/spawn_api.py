"""``paddle.distributed.spawn`` — multi-process launcher-as-a-function
(upstream python/paddle/distributed/spawn.py, UNVERIFIED).

Spawns ``nprocs`` python processes running ``func(*args)`` with the
paddle rank env set. The children are pinned to the CPU backend
UNCONDITIONALLY (``JAX_PLATFORMS=cpu``), also on a TPU host: this is a
simulation of a multi-process job (the CPU/Gloo-role path), never a way
onto the accelerator — a chip belongs to one process, and in real runs
ONE process drives all the chips of a host."""

from __future__ import annotations

import multiprocessing
import os

__all__ = ["spawn"]


def _entry(func, rank, nprocs, args):
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_RANK": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_WORLD_SIZE": str(nprocs),
        "JAX_PLATFORMS": "cpu",     # read by jax at backend init
    })
    func(*args)


def spawn(func, args=(), nprocs=1, join=True, daemon=False, **options):
    """Run ``func(*args)`` in ``nprocs`` fresh processes. Returns the
    context (list of processes); with ``join=True`` waits and raises if
    any worker failed."""
    ctx = multiprocessing.get_context("spawn")
    procs = []
    for rank in range(int(nprocs)):
        p = ctx.Process(target=_entry, args=(func, rank, nprocs, args),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        bad = [i for i, p in enumerate(procs) if p.exitcode != 0]
        if bad:
            raise RuntimeError(
                f"paddle.distributed.spawn: ranks {bad} exited nonzero")
    return procs
