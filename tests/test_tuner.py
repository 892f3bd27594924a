"""Autotuner subsystem tests (ISSUE 4).

Fast tier: cache lifecycle under fault injection (atomic commit,
corrupt-discard-and-retune), deterministic engine behavior on a
synthetic cost table (no timing, no TPU), precedence (flag > override
> cache > default), surface registry contracts, the set_config entry
point, and the CI budget/hygiene tools.

Slow tier (breadth, per the fast-gate budget contract): real sweeps
through the CLI and kernels executing under tuned configs.
"""

import errno
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu import tuner
from paddle_tpu.testing import FaultInjector
from paddle_tpu.tuner import cache as tcache
from paddle_tpu.tuner import engine as tengine
from paddle_tpu.tuner.surface import TunableSurface


@pytest.fixture
def gcache(tmp_path):
    """Point the PROCESS-GLOBAL cache at a private file; restore the
    suite's hermetic cache afterwards (conftest sets the env var)."""
    c = tuner.set_cache_path(str(tmp_path / "cache.json"))
    yield c
    tuner.clear_overrides()
    tuner.set_tune_on_first_call(False)
    tuner.enable()
    tuner.set_cache_path(os.environ["PADDLE_TPU_TUNER_CACHE"])


def _synthetic_surface(name="syn_surface", with_cost=False):
    cost = None
    if with_cost:
        # bytes differ 1000x between a=1/2 and a=3: the roofline lower
        # bound PROVES a=3 worse than prune_ratio x the floor
        cost = lambda config, shape: (0.0,
                                      1e12 if config["a"] == 3 else 1e9)
    return tuner.register_surface(TunableSurface(
        name=name, params=("a",), default={"a": 1},
        candidates=lambda shape: [{"a": 1}, {"a": 2}, {"a": 3}],
        cost_fn=cost))


# -- cache lifecycle ---------------------------------------------------------

def test_cache_roundtrip_and_backend_namespace(tmp_path):
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path)
    k_tpu = tcache.make_key("gmm", "d64,h128", "bfloat16", "tpu:v5e")
    k_cpu = tcache.make_key("gmm", "d64,h128", "bfloat16", "cpu:cpu")
    c.put(k_tpu, {"bn": 1024}, median_ms=1.0)
    c.put(k_cpu, {"bn": 512}, median_ms=9.0, representative=False)
    # namespaces never cross: CPU trials cannot poison TPU configs
    fresh = tcache.TuningCache(path)
    assert fresh.lookup("gmm", "d64,h128", "bfloat16",
                        "tpu:v5e") == {"bn": 1024}
    assert fresh.lookup("gmm", "d64,h128", "bfloat16",
                        "cpu:cpu") == {"bn": 512}
    assert fresh.lookup("gmm", "d64,h128", "float32", "tpu:v5e") is None
    assert fresh.get(k_cpu)["representative"] is False
    assert len(fresh) == 2 and not fresh.discarded_corrupt


@pytest.mark.fault
def test_cache_atomic_write_under_enospc(tmp_path):
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path)
    with FaultInjector() as fi:
        fi.fail_write("c.json.part", errno_=errno.ENOSPC)
        c.put("k", {"bn": 256})
        assert fi.fires() == 1          # first write ENOSPCed, retry won
    assert tcache.TuningCache(path).get("k")["config"] == {"bn": 256}


@pytest.mark.fault
def test_cache_atomic_write_under_eio_rename(tmp_path):
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path)
    c.put("k0", {"bn": 128})
    with FaultInjector() as fi:
        fi.fail("c.json", op="rename", errno_=errno.EIO)
        c.put("k1", {"bn": 2048})
        assert fi.fires() == 1
    fresh = tcache.TuningCache(path)
    assert fresh.get("k0") and fresh.get("k1")


@pytest.mark.fault
def test_cache_truncated_write_detected(tmp_path):
    """A silent short write (kernel lies, success reported) must not
    commit a torn cache: the staged-size check catches it, the retry
    rewrites in full."""
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path)
    with FaultInjector() as fi:
        fi.truncate_write("c.json.part", after_bytes=10)
        c.put("k", {"bn": 512})
        assert fi.fires() == 1
    fresh = tcache.TuningCache(path)
    assert not fresh.discarded_corrupt
    assert fresh.get("k")["config"] == {"bn": 512}


@pytest.mark.fault
def test_cache_persistent_failure_keeps_old_file_and_memory(tmp_path):
    """When every retry fails, save_best_effort warns, the PREVIOUS
    on-disk cache stays intact (stage-then-rename: the target is never
    opened for writing) and the new entry still serves in-memory."""
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path)
    c.put("old", {"bn": 64})
    with FaultInjector() as fi:
        fi.fail_write("c.json.part", errno_=errno.ENOSPC, times=99)
        with pytest.warns(UserWarning, match="could not persist"):
            c.put("new", {"bn": 128}, persist=False)
            assert c.save_best_effort() is False
        assert fi.fires() >= 1
    assert c.get("new")["config"] == {"bn": 128}      # in-memory serves
    fresh = tcache.TuningCache(path)
    assert fresh.get("old") and fresh.get("new") is None


@pytest.mark.parametrize("corruption", [
    "",                                           # empty file
    "{not json at all",                           # torn JSON
    '{"version": 99, "entries": {}, "checksum": ""}',   # wrong schema
    '{"entries": "nope", "version": 1}',          # wrong shape
])
def test_corrupt_cache_discarded_never_crashed_on(tmp_path, corruption):
    path = tmp_path / "c.json"
    path.write_text(corruption)
    with pytest.warns(UserWarning, match="discarding corrupt"):
        c = tcache.TuningCache(str(path))
    assert len(c) == 0 and c.discarded_corrupt


def test_tampered_entries_fail_checksum(tmp_path):
    path = tmp_path / "c.json"
    c = tcache.TuningCache(str(path))
    c.put("k", {"bn": 512})
    raw = json.loads(path.read_text())
    raw["entries"]["k"]["config"]["bn"] = 9999     # bit rot / hand edit
    path.write_text(json.dumps(raw))
    with pytest.warns(UserWarning, match="checksum"):
        fresh = tcache.TuningCache(str(path))
    assert len(fresh) == 0 and fresh.discarded_corrupt


def test_corrupt_cache_discard_then_retune(tmp_path, gcache):
    """The discard-and-retune path end to end: corrupt file -> empty
    cache -> a search repopulates and commits a VALID file."""
    _synthetic_surface("syn_retune")
    with open(gcache.path, "w") as f:
        f.write('{"version": 1, "entries": {"k": ')   # torn mid-write
    with pytest.warns(UserWarning, match="discarding corrupt"):
        gcache.load()
    table = {1: 3.0, 2: 1.0, 3: 2.0}
    eng = tengine.TrialEngine(gcache)
    res = eng.search("syn_retune", {"d": 64},
                     measure_fn=lambda cfg, shape: table[cfg["a"]])
    assert res.best_config == {"a": 2}
    fresh = tcache.TuningCache(gcache.path)
    assert not fresh.discarded_corrupt
    assert fresh.lookup("syn_retune", "d64", "bfloat16",
                        eng.backend) == {"a": 2}


# -- trial engine (deterministic, no timing) ---------------------------------

def test_engine_picks_known_best_from_synthetic_cost_table(gcache):
    _synthetic_surface("syn_best")
    table = {1: 5.0, 2: 0.5, 3: 2.0}
    measured = []

    def measure(cfg, shape):
        measured.append(cfg["a"])
        return table[cfg["a"]]

    eng = tengine.TrialEngine(gcache)
    res = eng.search("syn_best", {"n": 8}, measure_fn=measure)
    assert res.best_config == {"a": 2}
    assert res.best_ms == pytest.approx(500.0)     # seconds -> ms
    assert measured == [1, 2, 3]                   # default tried first
    assert not res.cached_hit
    # second search resumes from cache without measuring
    measured.clear()
    res2 = eng.search("syn_best", {"n": 8}, measure_fn=measure)
    assert res2.cached_hit and res2.best_config == {"a": 2}
    assert measured == []
    # --force re-tunes
    res3 = eng.search("syn_best", {"n": 8}, measure_fn=measure,
                      force=True)
    assert not res3.cached_hit and measured == [1, 2, 3]


def test_engine_isolates_failing_candidates(gcache):
    """One candidate that raises (VMEM overflow, legalization error)
    is dropped with a warning; the search still commits a winner from
    the candidates that ran."""
    _synthetic_surface("syn_error")
    table = {1: 5.0, 3: 2.0}

    def measure(cfg, shape):
        if cfg["a"] == 2:
            raise RuntimeError("candidate blew VMEM")
        return table[cfg["a"]]

    with pytest.warns(UserWarning, match="candidate.*failed"):
        res = tengine.TrialEngine(gcache).search(
            "syn_error", {"n": 8}, measure_fn=measure)
    assert res.best_config == {"a": 3}
    assert gcache.get(res.key)["errored"] == 1
    # every candidate failing is still a hard error (nothing to commit)
    _synthetic_surface("syn_allfail")
    with pytest.warns(UserWarning):
        with pytest.raises(RuntimeError, match="no candidate"):
            tengine.TrialEngine(gcache).search(
                "syn_allfail", {"n": 8},
                measure_fn=lambda c, s: (_ for _ in ()).throw(
                    RuntimeError("boom")))


def test_engine_roofline_pruning_skips_provably_worse(gcache):
    _synthetic_surface("syn_prune", with_cost=True)
    measured = []

    def measure(cfg, shape):
        measured.append(cfg["a"])
        return 1.0

    from paddle_tpu.profiler.cost import Peaks
    res = tengine.TrialEngine(      # the CPU has no published peak
        gcache, peaks=Peaks(197e12, 819e9, "v5e")).search(
        "syn_prune", {"n": 8}, measure_fn=measure)
    assert 3 not in measured                # pruned before measuring
    assert sorted(measured) == [1, 2]
    assert [c["a"] for c, _ in res.pruned] == [3]


def test_engine_max_trials_reports_truncation(gcache):
    _synthetic_surface("syn_trunc")
    res = tengine.TrialEngine(gcache).search(
        "syn_trunc", {"n": 8}, measure_fn=lambda c, s: float(c["a"]),
        max_trials=2)
    assert res.truncated == 1               # never a silent cap
    assert res.best_config == {"a": 1}      # default kept (first)
    assert gcache.get(res.key)["truncated"] == 1


def test_engine_flags_non_representative_backend(gcache, monkeypatch):
    _synthetic_surface("syn_cpu")
    monkeypatch.setattr(tengine, "_non_tpu_warned", False)
    with pytest.warns(UserWarning, match="non-TPU backend"):
        res = tengine.TrialEngine(gcache).search(
            "syn_cpu", {"n": 8}, measure_fn=lambda c, s: 1.0)
    assert res.backend.startswith("cpu:")
    assert res.representative is False
    assert gcache.get(res.key)["representative"] is False
    # warned ONCE: a second search stays quiet
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        tengine.TrialEngine(gcache).search(
            "syn_cpu", {"n": 9}, measure_fn=lambda c, s: 1.0)


def test_surface_grid_default_first_and_validity():
    s = TunableSurface(
        name="syn_grid_local", params=("a",), default={"a": 2},
        candidates=lambda shape: [{"a": 1}, {"a": 2}, {"a": 4}],
        is_valid=lambda c, shape: c["a"] <= shape.get("cap", 99))
    grid = s.grid({"cap": 2})
    assert grid[0] == {"a": 2}              # default leads
    assert grid == [{"a": 2}, {"a": 1}]     # a=4 invalid at cap=2


# -- lookup precedence -------------------------------------------------------

def test_lookup_precedence_override_beats_cache_beats_default(gcache):
    _synthetic_surface("syn_prec")
    backend = tcache.backend_signature()
    assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") is None
    gcache.put(tcache.make_key("syn_prec", "n4", "bfloat16", backend),
               {"a": 2})
    assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") == {"a": 2}
    tuner.set_override("syn_prec", {"a": 3})
    assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") == {"a": 3}
    tuner.set_override("syn_prec", None)
    assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") == {"a": 2}
    tuner.disable()
    try:
        assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") is None
        # disabled means STATIC DEFAULTS, even for pinned overrides
        # (they stay registered, dormant until re-enabled)
        tuner.set_override("syn_prec", {"a": 3})
        assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") is None
    finally:
        tuner.enable()
    assert tuner.lookup("syn_prec", {"n": 4}, "bfloat16") == {"a": 3}
    tuner.set_override("syn_prec", None)


def test_flash_flag_precedence_explicit_beats_cache(gcache):
    """Satellite: FLAGS_flash_attn_block_q/kv set explicitly (env or
    set_flags) must win over tuner-cache values; unset flags yield to
    the cache; the cache yields to the flag defaults."""
    from paddle_tpu.framework import flags
    from paddle_tpu.ops.pallas.flash_attention import _resolve_blocks
    backend = tcache.backend_signature()
    # defaults when neither cache nor explicit flags speak
    assert flags.flag_source("FLAGS_flash_attn_block_q") == "default"
    assert _resolve_blocks(4096, 4096, 64, "bfloat16") == (256, 512)
    gcache.put(tcache.make_key("flash_attention", "d64,sk4096,sq4096",
                               "bfloat16", backend),
               {"block_q": 128, "block_kv": 1024})
    assert _resolve_blocks(4096, 4096, 64, "bfloat16") == (128, 1024)
    # explicit set_flags wins per-knob; the other still rides the cache
    ent = flags._registry["FLAGS_flash_attn_block_q"]
    prev = (ent["value"], ent["source"])
    try:
        flags.set_flags({"FLAGS_flash_attn_block_q": 512})
        assert flags.flag_source("FLAGS_flash_attn_block_q") == "set"
        assert _resolve_blocks(4096, 4096, 64, "bfloat16") == (512, 1024)
    finally:
        ent["value"], ent["source"] = prev      # restore default-ness
    assert _resolve_blocks(4096, 4096, 64, "bfloat16") == (128, 1024)


def test_flag_source_tracking(monkeypatch):
    from paddle_tpu.framework import flags
    flags.define_flag("FLAGS_tuner_test_plain", 7)
    assert flags.flag_source("FLAGS_tuner_test_plain") == "default"
    flags.set_flags({"FLAGS_tuner_test_plain": 8})
    assert flags.flag_source("FLAGS_tuner_test_plain") == "set"
    monkeypatch.setenv("FLAGS_tuner_test_env", "11")
    flags.define_flag("FLAGS_tuner_test_env", 7)
    assert flags.flag_source("FLAGS_tuner_test_env") == "env"
    assert flags.flag("FLAGS_tuner_test_env") == 11


# -- incubate.autotune entry point -------------------------------------------

def test_set_config_kernel_section(gcache, tmp_path):
    from paddle_tpu.incubate import autotune
    cache_path = str(tmp_path / "ac.json")
    autotune.set_config(kernel={
        "enable": True, "cache_path": cache_path,
        "configs": {"flash_attention": {"block_q": 512,
                                        "block_kv": 256}}})
    try:
        assert tuner.get_cache().path == cache_path
        assert tuner.lookup("flash_attention",
                            {"sq": 64, "sk": 64, "d": 64}) \
            == {"block_q": 512, "block_kv": 256}
        assert autotune.get_config()["kernel"]["enable"] is True
        autotune.set_config(kernel={"enable": True,
                                    "configs": {"flash_attention": None}})
        assert tuner.lookup("flash_attention",
                            {"sq": 64, "sk": 64, "d": 64}) is None
        autotune.set_config(kernel={"enable": False})
        assert not tuner.enabled()
        autotune.set_config()               # default: load-from-cache
        assert tuner.enabled() and not tuner.tune_on_first_call()
        with pytest.warns(UserWarning, match="unknown section"):
            autotune.set_config({"bogus": {}})
        with pytest.raises(TypeError):
            autotune.set_config(kernel={"configs": {"flash_attention":
                                                    [1, 2]}})
    finally:
        tuner.clear_overrides()


# -- registered surfaces (registry contracts) --------------------------------

def test_builtin_surfaces_registered():
    from paddle_tpu.tuner.sweeps import ensure_builtin_surfaces
    ensure_builtin_surfaces()
    names = tuner.list_surfaces()
    for required in ("grouped_matmul", "flash_attention", "rms_norm",
                     "scan_remat", "serving_chunks"):
        assert required in names
    gmm = tuner.get_surface("grouped_matmul")
    assert gmm.default == {"bn": 2048, "bd": 512, "bh": 2048}
    grid = gmm.grid({"d": 1024, "h": 1408, "E": 16})
    assert grid[0] == gmm.default
    assert all(c["bn"] % 128 == 0 for c in grid)
    # the cost model ranks small dw tiles memory-bound-worse
    f_small, b_small = gmm.cost_fn({"bn": 512, "bd": 128, "bh": 512},
                                   {"d": 1024, "h": 1408, "E": 16})
    f_big, b_big = gmm.cost_fn({"bn": 2048, "bd": 512, "bh": 2048},
                               {"d": 1024, "h": 1408, "E": 16})
    assert f_small == f_big and b_small > b_big


def test_scan_remat_surface_grid():
    from paddle_tpu.tuner.sweeps import ensure_builtin_surfaces
    ensure_builtin_surfaces()
    s = tuner.get_surface("scan_remat")
    doses = [c["full_save_interval"] for c in s.grid({"L": 12})]
    assert doses[0] == 0                    # default (plain remat) first
    assert set(doses) == {0, 1, 2, 3, 4, 6}  # all tile L=12
    doses7 = [c["full_save_interval"] for c in s.grid({"L": 7})]
    assert set(doses7) == {0, 1}            # nothing else tiles 7


def test_serving_chunks_surface_grid():
    from paddle_tpu.tuner.sweeps import ensure_builtin_surfaces
    ensure_builtin_surfaces()
    s = tuner.get_surface("serving_chunks")
    shape = {"slots": 8, "max_len": 64, "page": 16}
    grid = s.grid(shape)
    assert all(s.is_valid(c, shape) for c in grid)
    assert all(c["decode_chunk"] <= 64 and c["prefill_chunk"] <= 64
               and c["admit_batch"] <= 8 for c in grid)
    assert any(c["admit_batch"] == 1 for c in grid)


# -- CLI + tools -------------------------------------------------------------

def test_cli_list(capsys):
    from paddle_tpu.tuner.__main__ import main
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "grouped_matmul" in out and "serving_chunks" in out
    assert "model-level" in out


def test_cli_shape_parsing_errors():
    from paddle_tpu.tuner.__main__ import _parse_shape, main
    assert _parse_shape("d=64, h=128,E=4") == {"d": 64, "h": 128, "E": 4}
    with pytest.raises(SystemExit):
        _parse_shape("d64")
    with pytest.raises(SystemExit):
        main([])                            # nothing to do
    with pytest.raises(SystemExit):
        main(["--surface", "grouped_matmul"])   # missing --shape


def test_cli_model_level_surface_points_at_bench(tmp_path, capsys):
    from paddle_tpu.tuner.__main__ import main
    rc = main(["--surface", "serving_chunks", "--shape",
               "slots=4,max_len=64,page=16",
               "--cache", str(tmp_path / "c.json")])
    assert rc == 2
    assert "bench.py" in capsys.readouterr().err


def test_check_atomic_writes_covers_tuner_package():
    import importlib.util
    import pathlib
    checker = (pathlib.Path(__file__).resolve().parent.parent
               / "tools" / "check_atomic_writes.py")
    spec = importlib.util.spec_from_file_location("caw", checker)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert any("tuner" in r for r in mod.DEFAULT_ROOTS)
    assert mod.main() == 0                  # both packages clean


def test_check_fast_tier_budget(tmp_path, capsys):
    import importlib.util
    import pathlib
    tool = (pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "check_fast_tier_budget.py")
    spec = importlib.util.spec_from_file_location("cftb", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.parse_duration_s(
        "8 failed, 606 passed, 1 error in 115.60s (0:01:55)") == 115.60
    assert mod.parse_duration_s("no summary here") is None
    ok = tmp_path / "ok.log"
    ok.write_text("606 passed in 120.0s\n")
    over = tmp_path / "over.log"
    over.write_text("= 700 passed, 2 warnings in 471.55s (0:07:51) =\n")
    assert mod.main(["--log", str(ok)]) == 0
    assert mod.main(["--log", str(over)]) == 1
    assert mod.main(["--log", str(tmp_path / "missing.log")]) == 2
    bad = tmp_path / "bad.log"
    bad.write_text("pytest crashed before any summary\n")
    assert mod.main(["--log", str(bad)]) == 2
    # warn zone: within budget but past the tripwire
    capsys.readouterr()
    assert mod.main(["--log", str(ok), "--budget", "130"]) == 0
    assert "WARNING" in capsys.readouterr().err


# -- kernels under tuned configs (breadth: slow tier) ------------------------

@pytest.mark.slow
def test_grouped_matmul_runs_correct_under_tuned_tiles(gcache):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas.grouped_matmul import (_tile_config,
                                                      grouped_matmul)
    backend = tcache.backend_signature()
    gcache.put(tcache.make_key("grouped_matmul", "E2,d64,h128",
                               "float32", backend),
               {"bn": 128, "bd": 128, "bh": 128})
    assert _tile_config((2, 64, 128), "float32") \
        == {"bn": 128, "bd": 128, "bh": 128}
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(256, 64), jnp.float32)
    w = jnp.asarray(rng.randn(2, 64, 128), jnp.float32)
    gid = jnp.asarray([0, 1], jnp.int32)

    def loss(x, w):
        return grouped_matmul(x, w, gid).sum()    # tuned tiles resolve

    y = grouped_matmul(x, w, gid)
    ref = jnp.concatenate([x[:128] @ w[0], x[128:] @ w[1]])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    ref_gw0 = x[:128].T @ jnp.ones((128, 128), jnp.float32)
    np.testing.assert_allclose(np.asarray(gw[0]), np.asarray(ref_gw0),
                               rtol=2e-4, atol=2e-4)
    assert gx.shape == x.shape


@pytest.mark.slow
def test_cli_sweep_resumable_end_to_end(tmp_path):
    """Real CLI sweep (interpret-mode Pallas on CPU): commits a winner
    atomically, then a re-run resumes (skips the cached key)."""
    cache_path = str(tmp_path / "cli.json")
    cmd = [sys.executable, "-m", "paddle_tpu.tuner",
           "--surface", "rms_norm", "--shape", "d=128",
           "--cache", cache_path, "--repeats", "1", "--warmup", "0",
           "--max-candidates", "2"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["surface"] == "rms_norm" and not rec["cached_hit"]
    assert rec["representative"] is False   # CPU trials flagged
    assert rec["truncated"] >= 1            # cap reported, not silent
    raw = json.loads(open(cache_path).read())
    assert raw["version"] == tcache.CACHE_VERSION
    [key] = [k for k in raw["entries"] if k.startswith("rms_norm|")]
    assert key.split("|")[-1].startswith("cpu:")   # backend namespace
    r2 = subprocess.run(cmd, capture_output=True, text=True, env=env,
                        cwd=os.path.dirname(os.path.dirname(
                            os.path.abspath(__file__))))
    assert r2.returncode == 0, r2.stderr
    rec2 = json.loads(r2.stdout.strip().splitlines()[-1])
    assert rec2["cached_hit"] and rec2["config"] == rec["config"]


@pytest.mark.slow
def test_serving_engine_consults_chunk_cache(gcache):
    import numpy as np
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    import paddle_tpu as paddle
    backend = tcache.backend_signature()
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    dtype = next(iter(model.parameters()))._data.dtype
    gcache.put(tcache.make_key("serving_chunks",
                               "max_len48,page8,slots2", str(dtype),
                               backend),
               {"decode_chunk": 8, "prefill_chunk": 16,
                "admit_batch": 1})
    eng = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                   max_len=48, greedy=True)
    assert eng.decode_chunk == 8            # cache served the ladder
    assert eng.prefill_chunk == 16
    assert eng.admit_batch == 1
    # explicit argument beats the cache
    eng2 = ContinuousBatchingEngine(model, num_slots=2, page_size=8,
                                    max_len=48, decode_chunk=4,
                                    greedy=True)
    assert eng2.decode_chunk == 4
    # and the tuned engine actually serves
    rng = np.random.RandomState(0)
    eng.add_request(rng.randint(0, 64, (6,)).astype(np.int32), 4)
    done = eng.run()
    assert len(done) == 1 and len(done[0].tokens) == 4


@pytest.mark.slow
def test_tune_on_first_call_rms_norm(gcache):
    """set_config(kernel={tune_on_first_call}) really searches on a
    miss and commits: the second lookup is a pure cache hit."""
    from paddle_tpu.incubate import autotune
    autotune.set_config(kernel={"enable": True,
                                "tune_on_first_call": True,
                                "cache_path": gcache.path})
    try:
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            got = tuner.lookup("rms_norm", {"d": 64}, "float32")
        assert got is not None and got["block_rows"] % 8 == 0
        entry = tuner.get_cache().lookup("rms_norm", "d64", "float32")
        assert entry == got
    finally:
        tuner.set_tune_on_first_call(False)
        tuner.set_cache_path(os.environ["PADDLE_TPU_TUNER_CACHE"])
