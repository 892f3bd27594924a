"""The benchmark's own tests: ``JAX_PLATFORMS=cpu python -m pytest
perfbench/tests -q -p no:cacheprovider``. Not part of tier-1 (``tests/``)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


class FakeChip:
    """Stands where ``jax.devices()[0]`` would: the tests skip the harness's
    look for a chip and drive the rest of a run. Nothing a test computes is
    a device metric."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def load_cfg(name):
    from perfbench.harness import spec
    with open(os.path.join(DATA, name)) as f:
        cfg = json.load(f)
    cfg["sizes"] = spec.sizes(cfg)
    return cfg


def load_traffic(name):
    from perfbench.harness import traffic
    return traffic.load(os.path.join(DATA, name))


@pytest.fixture(scope="session")
def bench():
    from perfbench.harness import spec
    return spec.load_benchmark(ROOT)


def drive(bench, cfg, traffic, cell_index, seed=2 ** 31 + 11, seconds=2.0):
    """run_cell at a tiny size, as run.py would after finding its chip."""
    import time
    from perfbench.harness import main as M
    t0 = time.perf_counter()
    return M.run_cell(bench, bench["workloads"][cell_index], cfg, traffic,
                      seed, seconds, 0, t0, ROOT, [FakeChip()],
                      M.make_say(t0))
