"""The MiniCPM-SALA cell ``minicpm-sala-d8.long_doc_closed``: its entries in
``BENCHMARK.json``, the configuration file against the catalog and the
issue's byte count, the work counters of ``harness/minicpm_sala.py`` against
hand counts (the rule's keys per query, the distinct K/V of a pass, the
step kernel's bytes for the rows that advanced), its readers on a program
without the kernels or the counters, the reference against the tiny
program, and the cell end to end on the CPU at the tiny size."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.harness import flops, peaks, spec, weights
from perfbench.harness import minicpm_sala as X

from conftest import ROOT, drive, load_cfg, load_traffic

CELL = "minicpm-sala-d8.long_doc_closed"
CONFIG = "minicpm-sala-d8"
NEW = ["step.serve_mfu.minicpm_sala",
       "kernel.sparse_attn_roofline.minicpm_sala",
       "kernel.linear_state_step_roofline.minicpm_sala",
       "sparse.blocks_loaded_per_selected.minicpm_sala"]
SHARED = ["serve_tok_s", "sched.slot_occupancy.batch",
          "device.idle_share.batch", "pump.idle_ms_dispatch.batch",
          "pump.idle_ms_harvest.batch", "sched.idle_ms_admit_drain.batch",
          "pump.idle_ms_other.batch", "step.prefill_fill.batch"]


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_the_cell_is_the_one_the_issue_names(bench):
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "long_doc_closed", 1)
    # the sixth cell and configuration (by name, not "last")
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 5
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 5
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    e2e = {mt["name"] for mt in spec.cell_metrics(bench, cell, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    for mt in bench["end_to_end"] + bench["per_layer"]:
        if CELL in mt.get("workloads", ()):
            assert mt["name"] in SHARED + NEW
            assert mt["workloads"][-1] == CELL
    at = [mt["name"] for mt in bench["per_layer"]].index(NEW[0])
    assert [mt["name"] for mt in bench["per_layer"][at:at + 4]] == NEW
    for mt in bench["per_layer"][at:at + 4]:
        assert mt["workloads"] == [CELL] and mt["moves"] == "serve_tok_s"
    for name in SHARED:
        mt = next(x for x in bench["end_to_end"] + bench["per_layer"]
                  if x["name"] == name)
        assert CELL in mt["workloads"]


@pytest.fixture(scope="module")
def cfg(bench):
    return spec.load_config(ROOT, bench, CONFIG)


@pytest.fixture(scope="module")
def m(cfg):
    return cfg["sizes"]


def test_the_file_counts_the_parameters_the_issue_counts(m):
    from perfbench.reference import minicpm_sala as R
    by = {}
    for name, s, _ in R.param_specs(m):
        l = name.split(".")[1] if name.startswith("layers.") else name
        by[l] = by.get(l, 0) + int(np.prod(s))
    assert X.kinds(m) == ["lightning-attn"] * 4 + ["minicpm4"] * 2 \
        + ["lightning-attn"] * 2
    assert by["0"] == 285_225_216 and by["4"] == 253_763_840
    assert sum(by.values()) == 2_820_569_088          # 5.64 GB bf16
    assert by["embed_tokens.weight"] + by["lm_head.weight"] \
        + by["norm.weight"] == 601_690_112


def test_flops_of_one_token(m):
    assert X.sparse_matmul_params(m) == 4096 * (2 * 4096 + 2 * 256) \
        + 4096 * 4096
    assert X.lightning_matmul_params(m) == 5 * 4096 * 4096
    assert X.lightning_rule_flops_token(m) == 5 * 32 * 128 * 128
    assert X.layers_flops_token(m) == 2 * 2 * X.sparse_matmul_params(m) \
        + 6 * (2 * 5 * 4096 * 4096 + 5 * 32 * 128 * 128) \
        + 8 * 6 * 4096 * 16384
    # a query attends p + 1 keys to the dense length, then 63 whole blocks
    # and the one that holds p
    assert list(X.keys_attended(m, [0, 8191, 8192, 16384 + 5])) \
        == [1, 8192, 63 * 64 + 1, 63 * 64 + 6]
    assert list(X.entries_scored(m, [8191, 8192, 8192 + 16])) \
        == [0, (8192 - 31) // 16 + 1, (8192 + 16 - 31) // 16 + 1]
    per = 4 * 32 * 128
    assert X.attn_flops(m, 8190, 3) == 2 * (
        per * (8191 + 8192 + 63 * 64 + 1)
        + 2 * 32 * 128 * ((8192 - 31) // 16 + 1))


def test_the_issues_prefill_trip(m):
    """A trip of 2,048 prompt positions at 16k: the issue's ~9.1 TFLOP of
    matmuls, the attention's required work beside it."""
    mat = 2048 * X.layers_flops_token(m)
    assert mat == pytest.approx(9.1e12, rel=0.05)
    f, b = X.sparse_attention_work(m, [(16384, 2048)], kv_bytes=2)
    assert f == 2 * 4 * 32 * 128 * float(np.sum(X.keys_attended(
        m, np.arange(16384, 16384 + 2048))))
    # the history is the smaller: every key of the slot, once a pass
    assert b == 2 * (2 * 2 * 128 * (16384 + 2048) * 2
                     + 2 * 2048 * 32 * 128 * 2)
    # a decode step of one query reads its own 64 blocks, not the history
    _, b = X.sparse_attention_work(m, [(20000, 1)], kv_bytes=2)
    assert b == 2 * (2 * 2 * 128 * (63 * 64 + 20000 % 64 + 1) * 2
                     + 2 * 32 * 128 * 2)


def test_the_step_kernel_is_charged_for_the_rows_that_advanced(m):
    pk = peaks.peaks_for("TPU v5 lite")
    f, b = X.linear_state_step_work(m, 16 * 6)
    assert f == 96 * 5 * 32 * 128 * 128
    assert b == 96 * 32 * (2 * 128 * 128 + 4 * 128) * 4
    assert flops.roofline_seconds(f, b, pk)[1] == "bandwidth"
    assert X.linear_state_step_work(m, 0) == (0, 0)


def _ctx(gauges, cfg=None):
    win = types.SimpleNamespace(gauges=gauges, turns=[], recs=[],
                                t_start=0.0, t_end=1.0)
    return {"kind": "serve", "win": win, "cfg": cfg or {}, "chunk": 2048}


@pytest.mark.parametrize("name", [n for n in NEW if "roofline" in n])
def test_a_roofline_reader_finds_nothing_without_its_kernel(name, cfg):
    """No trace, or a trace without the kernel's events (the parent commit
    has neither kernel): None, no raise."""
    rd = spec.load_metric_reader(name)
    fn = spec.resolve_reader(rd)
    ctx = _ctx({}, cfg)
    assert fn(rd, ctx) is None
    trace = types.SimpleNamespace(device_ops={"0": []})
    ctx.update(trace=trace, reduced={"window": (0.0, 1.0), "steps": 0},
               span=(0.0, 1.0))
    assert fn(rd, ctx) is None


def test_counter_readers_read_nothing_from_a_program_without_them(cfg):
    rd = spec.load_metric_reader("step.serve_mfu.minicpm_sala")
    assert spec.resolve_reader(rd)(rd, _ctx({}, cfg)) is None
    rd = spec.load_metric_reader(
        "sparse.blocks_loaded_per_selected.minicpm_sala")
    fn = spec.resolve_reader(rd)
    assert fn(rd, _ctx({})) is None
    assert fn(rd, _ctx({"sparse_blocks_selected": 0,
                        "sparse_blocks_loaded": 0})) is None
    assert fn(rd, _ctx({"sparse_blocks_selected": 640,
                        "sparse_blocks_loaded": 640})) == 1.0


def test_the_cells_metrics_name_readers_that_resolve(bench):
    cell = spec.find_cell(bench, CELL)
    names = [mt["name"] for mt in spec.cell_metrics(bench, cell, "per_layer")]
    assert set(NEW) | set(SHARED[1:]) <= set(names)
    assert not [n for n in names if "qwen3_next" in n or "moe." in n]
    for n in names:
        assert callable(spec.resolve_reader(spec.load_metric_reader(n)))


def test_the_configuration_file_states_its_cut(cfg):
    """Every key the file changed from the source is in ``reduced``, with
    its published value under ``published`` and a reason; the layers are
    the published entries 12-19 of ``mixer_types``; no width is cut."""
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) \
        == set(cfg["published"]) == {"num_hidden_layers", "mixer_types"}
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["mixer_types"] == cfg["published"]["mixer_types"][12:20]
    assert cfg["first_published_layer"] == 12
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["lightning_nh"], cfg["lightning_head_dim"],
            cfg["vocab_size"]) == (4096, 16384, 32, 2, 128, 32, 128, 73448)
    for k in ("assumed", "deployment", "memory_reckoning", "check_why"):
        assert cfg[k]
    e = cfg["engine"]
    assert (e["num_slots"], e["max_len"], e["page_size"],
            e["prefill_chunk"], e["decode_chunk"]) == (16, 33792, 64, 2048,
                                                       16)
    assert e["page_size"] == cfg["sparse_block_size"]


def test_the_traffic_file(bench, cfg):
    """The cell's medians and clips (prompts 16,384 in 8,448-32,768, outputs
    192 in 32-1,024) at narrowed, non-zero sigmas (why: the file's
    source_note): every prompt past dense_len, the longest request inside
    the engine's max_len, lengths that differ, and a warm-up that crosses
    the 2,048-key window."""
    from perfbench.harness import traffic as T
    t = spec.load_traffic("long_doc_closed")
    assert (t["kind"], t["clients"], t["pool"]) == ("closed", 24, 48)
    p, o = t["prompt"], t["output"]
    assert (p["median"], p["min"], p["max"]) == (16384, 8448, 32768)
    assert (o["median"], o["min"], o["max"]) == (192, 32, 1024)
    assert p["sigma"] > 0 and o["sigma"] > 0
    pl, ol = T.lengths(p, 48), T.lengths(o, 48)
    assert len(set(pl)) > 1 and len(set(ol)) > 1
    assert cfg["sparse_dense_len"] < pl.min()
    assert pl.max() + ol.max() <= cfg["engine"]["max_len"]
    assert t["warmup"]["prompt"] > 2048


def test_the_reference_is_the_tiny_program():
    """The tiny configuration's program (engine-free, whole sequences) and
    the reference agree at float32 over positions that cross the window
    and the dense length."""
    from perfbench.harness import model as model_mod
    from perfbench.reference import minicpm_sala as R
    c = load_cfg("tiny-minicpm-sala.json")
    model, specs = model_mod.build(c, 7, lambda *a, **k: None)
    model.eval()
    ids = np.random.default_rng(1).integers(0, 128, (2, 48)).astype(
        np.int32)
    import paddle_tpu as paddle
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    src = weights.LeafSource(specs, weights.seed_words(7), c["init_std"],
                             jnp.float32, R.LAYER_PATTERN)
    want = np.asarray(R.logits(c["sizes"], src, jnp.asarray(ids)))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_closed_loop_serving_of_sparse_and_linear_layers(bench):
    tr = load_traffic("tiny_closed.json")
    res = drive(bench, load_cfg("tiny-minicpm-sala.json"), tr,
                [w["name"] for w in bench["workloads"]].index(CELL))
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["served_gap_max"]["value"] <= 1e-3
