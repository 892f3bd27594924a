"""Worker script for test_multiprocess.py — the SURVEY.md §4(c)
localhost-simulated multi-host bring-up: each process pins the CPU
backend, calls ``init_parallel_env`` (→ ``jax.distributed.initialize``
against the launcher-provided coordinator), then exercises the L8
control plane end-to-end: host-side object collective, barrier, and a
coordinated distributed-checkpoint save + reload.

Run via ``python -m paddle_tpu.distributed.launch --nproc_per_node 2
--master 127.0.0.1:<port> tests/mp_worker.py <tmpdir>`` (the test does
exactly this).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# multi-process CPU simulation: pin the backend before it initializes
jax.config.update("jax_platforms", "cpu")


def main():
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    out_dir = sys.argv[1]
    world = int(os.environ["PADDLE_TRAINERS_NUM"])

    dist.init_parallel_env()
    assert jax.process_count() == world, (
        f"jax.distributed bring-up failed: process_count="
        f"{jax.process_count()} != {world}")
    rank = dist.get_rank()
    assert rank == int(os.environ["PADDLE_TRAINER_ID"])

    # host-side object collective through the coordination service
    objs = []
    dist.all_gather_object(objs, {"rank": rank, "tag": "x" * (rank + 1)})
    assert [o["rank"] for o in objs] == list(range(world)), objs
    assert objs[world - 1]["tag"] == "x" * world

    # barrier: all ranks must pass together
    dist.barrier()

    # eager TENSOR collectives, host-mediated (the Gloo role): each op
    # must see every rank's contribution
    import paddle_tpu as _p
    x = _p.to_tensor(np.full((3,), float(rank + 1), np.float32))
    dist.all_reduce(x)
    np.testing.assert_allclose(
        np.asarray(x.numpy()),
        np.full((3,), sum(range(1, world + 1)), np.float32))
    parts = []
    dist.all_gather(parts, _p.to_tensor(
        np.full((2,), float(rank), np.float32)))
    assert len(parts) == world
    for r, t in enumerate(parts):
        np.testing.assert_allclose(np.asarray(t.numpy()),
                                   np.full((2,), float(r), np.float32))
    b = _p.to_tensor(np.full((2,), float(rank * 10 + 5), np.float32))
    dist.broadcast(b, src=0)
    np.testing.assert_allclose(np.asarray(b.numpy()),
                               np.full((2,), 5.0, np.float32))

    # coordinated distributed checkpoint: every rank saves its (replicated)
    # state, rank 0's metadata wins; then all reload and verify
    t = paddle.to_tensor(
        np.arange(8, dtype=np.float32) + 1.0)
    ckpt = {"w": t}
    dist.save_state_dict(ckpt, out_dir)
    dist.barrier()
    t2 = paddle.to_tensor(np.zeros(8, dtype=np.float32))
    target = {"w": t2}
    dist.load_state_dict(target, out_dir)
    np.testing.assert_allclose(np.asarray(target["w"].numpy()),
                               np.arange(8, dtype=np.float32) + 1.0)
    dist.barrier()

    # rank-stamped proof file the test asserts on
    with open(os.path.join(out_dir, f"ok.{rank}"), "w") as f:
        f.write(f"MP_WORKER_OK {rank}/{world}\n")
    print(f"MP_WORKER_OK {rank}/{world}")


if __name__ == "__main__":
    main()
