"""The Mellum 2 training cell ``mellum2-ep4-d4.code_pretrain_8k``: its
entries in ``BENCHMARK.json``, the configuration file against the cut's
parameter count and the catalog, the work counters of
``harness/mellum2.py`` against the sizing arithmetic (PERF.md section 4),
its readers on a small recorded trace and on a program without the counters
or the kernels, and the cell end to end on the CPU at the tiny size."""

import json
import os
import re
import types

import numpy as np
import pytest

from perfbench.harness import mellum2 as X
from perfbench.harness import peaks, spec

from conftest import ROOT, drive, load_cfg, load_traffic

CELL = "mellum2-ep4-d4.code_pretrain_8k"
CONFIG = "mellum2-ep4-d4"
NEW = ["step.train_mfu.mellum2", "kernel.flash_attn_roofline.mellum2",
       "kernel.grouped_matmul_roofline.mellum2",
       "moe.local_pairs_per_token.train", "moe.max_over_mean_load.train"]
SHARED = ["train_tok_s", "step.device_ms.train", "device.idle_share.train",
          "step.idle_ms_execute.train", "step.idle_ms_other.train",
          "step.fresh_outputs.train"]


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


@pytest.fixture(scope="module")
def m(bench):
    return spec.load_config(ROOT, bench, CONFIG)["sizes"]


def test_the_cell_and_its_entries(bench):
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "code_pretrain_8k", 1)
    assert bench["workloads"][-1] is cell       # added at the end
    assert bench["configs"][-1]["name"] == CONFIG
    e2e = {mt["name"] for mt in spec.cell_metrics(bench, cell, "end_to_end")}
    assert e2e == {"train_tok_s", "setup_s"}
    by = {mt["name"]: mt for mt in bench["end_to_end"] + bench["per_layer"]}
    for name in SHARED:
        assert by[name]["workloads"] == ["gpt2-small.pretrain_1k", CELL]
    for name in NEW:
        assert by[name]["workloads"] == [CELL]
        assert by[name]["moves"] == "train_tok_s"
    assert [mt["name"] for mt in bench["per_layer"][-len(NEW):]] == NEW
    # the accepted pattern counts every Pallas call: not this cell's
    assert CELL not in by["kernel.flash_attn_roofline"]["workloads"]
    layers = {mt["layer"] for mt in bench["per_layer"]}
    assert {by[n]["layer"] for n in NEW} <= layers
    mix = spec.load_traffic("code_pretrain_8k")
    assert (mix["kind"], mix["seq"], mix["batch"], mix["corpus_ids"]) == (
        "train", 8192, 2, 24576)


def test_the_entries_keep_the_contract(bench):
    """What ``test_contract.py`` asks of every entry, for the new ones."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    cfg = [c for c in bench["configs"] if c["name"] == CONFIG][0]
    cell = spec.find_cell(bench, CELL)
    for s in (cfg["why"], cell["why"], cfg["source"]):
        assert 1 <= len(s) <= 200 and "\n" not in s
    for mt in bench["per_layer"]:
        if mt["name"] in NEW:
            assert name.match(mt["name"])
            assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", mt["unit"])
            if "roofline" in mt["name"] or "mfu" in mt["name"]:
                assert mt["unit"] == "%" and mt["better"] == "higher"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_the_file_counts_the_parameters_of_the_cut(m):
    from perfbench.reference import mellum2 as R
    by = {}
    for n, s, _ in R.param_specs(m):
        key = n.split(".")[1] if n.startswith("layers.") else n
        by[key] = by.get(key, 0) + int(np.prod(s))
    hid = 2304
    attn = hid * 4096 * 2 + hid * 512 * 2
    assert attn == 21_233_664                    # q, k, v, o: 21.2 M
    assert X.expert_params(m) == 3 * hid * 896 == 6_193_152
    layer = attn + hid * 64 + 16 * 6_193_152 + 2 * hid
    assert by["0"] == by["3"] == layer
    assert round(layer / 1e6, 2) == 120.48       # a layer of the cut
    slices = 2 * 24576 * hid
    assert round(slices / 1e6, 1) == 113.2
    total = sum(by.values())
    assert round(total / 1e6, 1) == 595.2
    assert round(16 * total / 1e9, 2) == 9.52    # 16 B a parameter
    # the published model, for scale: 28 x (21.4 M + 64 experts) + 453 M
    published = 28 * (attn + hid * 64 + 64 * 6_193_152) + 2 * 98304 * hid
    assert round(published / 1e9, 2) == 12.15


def test_work_counters_give_the_sizing_arithmetic(m):
    assert X.expected_local_pairs(m) == 2.0
    matmul = 6 * (X.dense_matmul_params(m)
                  + 4 * 2.0 * X.expert_params(m))
    assert round(matmul / 1e9, 2) == 1.15        # GFLOP a token
    full = 3 * 4 * 4096 * X.keys_seen(8192) / 8192
    window = 3 * 4 * 4096 * X.keys_seen(8192, 1024) / 8192
    assert round(full / 1e6) == 201 and round(window / 1e6) == 47
    attn = 3 * X.attention_fwd_flops(m, 1, 8192) / 8192
    assert attn == pytest.approx(full + 3 * window)
    # 201 for the full layer + 3 x 47.3 for the window layers
    assert round(attn / 1e6) == 343
    per = X.train_flops_per_token(m, 8192, 2.0)
    assert per == pytest.approx(matmul + attn)
    assert round(per * 16384 / 1e12, 1) == 24.5  # a step of 2 x 8192
    # the window skip: attention as if every layer were causal
    causal = 3 * 4 * 4 * 4096 * X.keys_seen(8192) / 8192
    assert causal / attn > 2.3


def test_flash_and_expert_work_of_a_step(m):
    f, b = X.flash_attention_work(m, 2, 8192)
    assert f == pytest.approx(3 * X.attention_fwd_flops(m, 2, 8192))
    q_b, kv_b = 2 * 8192 * 4096 * 2, 2 * 8192 * 512 * 2
    assert b == 4 * 6 * (q_b + kv_b)
    fl, by = X.grouped_matmul_work(m, 1000, 4)
    assert fl == 6 * X.expert_params(m) * 1000
    assert by == 3 * 4 * 16 * X.expert_params(m) * 2 \
        + 3 * 1000 * 3 * (2304 + 896) * 2
    # a recomputed layer's second forward is work the kernels ran
    f2, b2 = X.flash_attention_work(m, 2, 8192, forwards=2)
    assert f2 == pytest.approx(4 / 3 * f)
    assert b2 == 4 * 8 * (q_b + kv_b)
    fl2, _ = X.grouped_matmul_work(m, 1000, 4, forwards=2)
    assert fl2 == 8 * X.expert_params(m) * 1000


def test_the_configuration_file_keeps_the_catalog(bench):
    """Every number of the catalog entry's config under the same key,
    changed only where ``reduced`` says, nested groups whole."""
    cfg = spec.load_config(ROOT, bench, CONFIG)
    path = os.path.join(ROOT, "perfbench", "tests", "data",
                        "mellum2-catalog.json")
    with open(path) as f:
        row = json.load(f)
    assert row["name"] == "Mellum2-12B-A2.5B-Instruct"
    assert cfg["source"] == row["source_url"]
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["num_experts"] == 16 and cfg["router_num_experts"] == 64
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:4]
    assert "4 chips" in cfg["deployment"]


def test_the_reckoning_is_of_the_chip(bench):
    cfg = spec.load_config(ROOT, bench, CONFIG)
    rec = cfg["memory_reckoning"]
    assert "595.2 M" in rec and "memory_peak_bytes" in rec
    for k in ("loss_rel", "grad_norm_gap", "grad2_norm_gap",
              "update_norm_gap"):
        assert 0 < cfg["check"][k] < 1
    for tag in ("(a)", "(b)", "(c)", "(d)"):
        assert tag in cfg["check_why"]


def _event(name, start, end):
    return types.SimpleNamespace(name=name, start=start, end=end)


def _ctx(m, trace=None, steps=2):
    run = types.SimpleNamespace(t_start=0.0, t_end=10.0,
                                step_ends=[float(i) for i in range(20)])
    ctx = {"kind": "train", "cfg": {"sizes": m},
           "traffic": {"batch": 2, "seq": 8192}, "peaks": peaks.peaks_for(
               "TPU v5 lite"), "chips": 1, "run": run}
    if trace is not None:
        ctx.update(trace=trace, reduced={"window": (0.0, 1.0),
                                         "steps": steps})
    return ctx


@pytest.fixture
def counters(monkeypatch):
    """The program's booked counters, as ``ops.moe`` would give them."""
    vals = {"tokens": 4.0e5, "local_pairs": 8.2e5, "busiest_pairs": 9.0e4}
    monkeypatch.setattr(X, "train_counters", lambda: dict(vals))
    return vals


RECORDED = [("%flash_attention_fwd.1 = bf16[64,8192,128] custom-call", 0.0,
             0.002),
            ("%jvp_flash_attention_fwd_.2 = bf16[64,8192,128] custom-call",
             0.002, 0.004),
            ("%flash_attention_dq.3 = bf16[64,8192,128] custom-call", 0.004,
             0.008),
            ("%flash_attention_dkv.4 = (bf16[64,8192,128]) custom-call",
             0.008, 0.014),
            ("%linear_ce_fwd.5 = f32[16384,8] custom-call", 0.014, 0.02),
            ("%grouped_matmul.6 = bf16[133248,896] custom-call", 0.02,
             0.03),
            ("%jvp_grouped_matmul_.7 = bf16[133248,896] custom-call", 0.03,
             0.04),
            ("%grouped_matmul.8 = bf16[133248,2304] custom-call", 0.04,
             0.045),
            ("%grouped_matmul_dw.9 = bf16[16,2304,896] custom-call", 0.045,
             0.05),
            ("%fusion.10 = f32[2304] fusion", 0.05, 0.06)]


def _recorded():
    return types.SimpleNamespace(device_ops={"0": [_event(*e)
                                                   for e in RECORDED]})


def test_the_readers_on_a_recorded_trace(m, counters):
    ctx = _ctx(m, _recorded())
    for name, secs in (("kernel.flash_attn_roofline.mellum2", 0.014),
                       ("kernel.grouped_matmul_roofline.mellum2", 0.03)):
        rd = spec.load_metric_reader(name)
        v = spec.resolve_reader(rd)(rd, ctx)
        note = ctx["notes"][rd["note"]]
        assert note["kernel_s"] == pytest.approx(secs)   # linear_ce apart
        assert note["events"] == 4
        assert note["forwards"] == 2     # the recomputed layer's second
        least = max(note["flops"] / 197e12, note["bytes"] / 819e9)
        assert v == pytest.approx(100 * least / secs)
    f, _ = X.flash_attention_work(m, 2, 8192, forwards=2)
    assert f == pytest.approx(4 * X.attention_fwd_flops(m, 2, 8192))
    assert ctx["notes"]["flash_attn"]["flops"] == pytest.approx(2 * f)
    pairs = 2 * 4 * 16384 * counters["local_pairs"] / counters["tokens"]
    assert ctx["notes"]["grouped_matmul"]["flops"] == pytest.approx(
        8 * X.expert_params(m) * pairs)


def test_the_counter_readers(m, counters):
    ctx = _ctx(m)
    rd = spec.load_metric_reader("moe.local_pairs_per_token.train")
    assert spec.resolve_reader(rd)(rd, ctx) == pytest.approx(2.05)
    rd = spec.load_metric_reader("moe.max_over_mean_load.train")
    assert spec.resolve_reader(rd)(rd, ctx) == pytest.approx(
        9.0e4 * 16 / 8.2e5)
    rd = spec.load_metric_reader("step.train_mfu.mellum2")
    per = X.train_flops_per_token(m, 8192, 2.05)
    assert spec.resolve_reader(rd)(rd, ctx) == pytest.approx(
        100 * per * 20 * 16384 / 10.0 / 197e12)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_or_kernels_reads_nothing(
        name, m, monkeypatch):
    """The parent commit has neither the counters nor the kernels' names:
    every new reader gives None and raises nothing."""
    monkeypatch.setattr(X, "train_counters", lambda: None)
    rd = spec.load_metric_reader(name)
    fn = spec.resolve_reader(rd)
    assert fn(rd, _ctx(m)) is None
    empty = types.SimpleNamespace(device_ops={"0": []})
    assert fn(rd, _ctx(m, empty)) is None
    assert fn(rd, _ctx(m, _recorded(), steps=0)) is None


def test_the_counters_are_read_from_the_registry():
    """``train_counters`` books the program's device-side sums and reads
    the process registry; nothing counted reads None."""
    from paddle_tpu.ops import moe
    c = X.train_counters()
    assert c is None or c["tokens"] > 0
    assert X.COUNTERS == moe.TRAIN_COUNTERS


def test_the_cell_end_to_end_at_the_tiny_size(bench):
    i = [w["name"] for w in bench["workloads"]].index(CELL)
    res = drive(bench, load_cfg("tiny-mellum2.json"),
                load_traffic("tiny_train.json"), i)
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_tok_s"]["value"] > 0
    c = X.train_counters()
    assert c["local_pairs"] / c["tokens"] == pytest.approx(2.0, rel=0.5)
