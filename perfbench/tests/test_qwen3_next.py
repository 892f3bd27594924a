"""The Qwen3-Next cell ``qwen3-next-ep8-d12.mixed_len_closed``: its entries
in ``BENCHMARK.json`` against what ISSUE 36 names, the configuration file
against the issue's byte count and the catalog, the work counters of
``harness/qwen3_next.py`` against hand counts (the delta rule at its
recurrent form's FLOPs, the step kernel's bytes for the rows that advanced),
its readers on a program without the counters or the kernel, and the cell end
to end on the CPU at the tiny size."""

import json
import os
import types

import numpy as np
import pytest

from perfbench.harness import flops, peaks, spec
from perfbench.harness import qwen3_next as X

from conftest import ROOT, drive, load_cfg, load_traffic

CELL = "qwen3-next-ep8-d12.mixed_len_closed"
CONFIG = "qwen3-next-ep8-d12"
NEW = ["step.serve_mfu.qwen3_next",
       "kernel.gated_delta_step_roofline.qwen3_next",
       "kernel.grouped_matmul_roofline.qwen3_next",
       "kernel.ragged_attn_roofline.qwen3_next",
       "gdn.chunk_token_share.qwen3_next"]


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_the_cell_is_the_one_the_issue_names(bench):
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed_len_closed", 1)
    # the fifth cell and configuration, after the four PR 35 left (by
    # position, not "last": a later PR appends after it)
    assert bench["workloads"][4] is cell
    assert bench["configs"][4]["name"] == CONFIG
    assert bench["configs"][4]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    e2e = {mt["name"] for mt in spec.cell_metrics(bench, cell, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    for mt in bench["end_to_end"] + bench["per_layer"]:
        if CELL in mt.get("workloads", ()):
            # appended after the K-EXAONE cell's name, nothing moved
            assert mt["workloads"][-2:] == [
                "k-exaone-ep8-d5.mixed_len_closed", CELL] \
                or mt["workloads"] == [CELL]
    at = [mt["name"] for mt in bench["per_layer"]].index(NEW[0])
    assert [mt["name"] for mt in bench["per_layer"][at:at + 5]] == NEW
    for mt in bench["per_layer"][at:at + 5]:
        assert mt["workloads"] == [CELL] and mt["moves"] == "serve_tok_s"


@pytest.fixture(scope="module")
def m(bench):
    return spec.load_config(ROOT, bench, CONFIG)["sizes"]


def test_the_file_counts_the_parameters_the_issue_counts(m):
    from perfbench.reference import qwen3_next as R
    by = {}
    for name, s, _ in R.param_specs(m):
        l = name.split(".")[1] if name.startswith("layers.") else name
        by[l] = by.get(l, 0) + int(np.prod(s))
    lin = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048 + 32 + 32 + 128
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert (lin, full) == (33_718_464, 27_263_488)      # 33.72 M, 27.26 M
    assert X.expert_params(m) == 3 * 2048 * 512 == 3_145_728
    moe = 2048 * 512 + 65 * 3_145_728 + 2048            # router, 64 + 1, gate
    assert by["0"] == lin + moe + 2 * 2048
    assert by["3"] == full + moe + 2 * 2048
    assert sum(by.values()) == 2_929_374_400            # 2.93 B = 5.86 GB bf16
    assert X.expected_local_pairs(m) == 1.25
    assert (X.n_linear(m), X.n_full(m)) == (9, 3)


def test_flops_of_one_token(m):
    # the rule: 32 heads x 6 x 128 x 128, whatever form runs
    assert X.gdn_rule_flops_token(m) == 6 * 32 * 128 * 128 == 3_145_728
    assert X.gdn_flops_token(m) == 2 * (2048 * 12288 + 2048 * 64
                                        + 4096 * 2048) \
        + 2 * 4 * 8192 + 3_145_728
    # q AND its gate: 2048 x 8192
    assert X.attn_matmul_params(m) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048
    dense = 2048 * 512 + 2048 + 3_145_728
    assert X.moe_flops_token(m) == 2 * dense + 2 * 3_145_728 * 1.25
    assert X.moe_flops_token(m, 0) == 2 * dense
    assert X.layers_flops_token(m) == 9 * X.gdn_flops_token(m) \
        + 3 * 2 * X.attn_matmul_params(m) + 12 * X.moe_flops_token(m)
    # the issue's "0.87 GFLOP a token outside the experts, 0.09 in the
    # local pairs, 0.03 in the delta rule's recurrent form"
    rule = 9 * X.gdn_rule_flops_token(m)
    assert rule == pytest.approx(0.028e9, rel=0.02)
    assert X.layers_flops_token(m, 0) - rule \
        == pytest.approx(0.87e9, rel=0.01)
    assert 12 * 2 * 3_145_728 * 1.25 == pytest.approx(0.094e9, rel=0.01)


def test_attention_counts_the_full_layers_alone(m):
    per_key = 4 * 16 * 256
    assert X.attn_flops(m, 1500, 1) == 3 * per_key * 1501
    layer, head = X.layers_flops_token(m), 2 * 2048 * 18992
    got = X.serve_flops(m, [(0, 10)], [None, 11])
    assert got == pytest.approx(10 * layer + 3 * per_key * 55 + head
                                + head + layer + 3 * per_key * 11)
    kv = 2 * 2 * 256 * 2                      # K and V of one key, bf16
    qo = 2 * 16 * 256 * 2                     # q in and context out
    f, b = X.ragged_attention_work(m, [(1500, 1), (7, 0)], kv_bytes=2)
    assert f == 3 * per_key * 1501
    assert b == 3 * (kv * 1501 + qo)
    _, b = X.ragged_attention_work(m, [(1000, 128)], kv_bytes=2)
    assert b == 3 * (kv * 1128 + 128 * qo)


def test_the_step_kernel_is_charged_for_the_rows_that_advanced(m):
    """40 slots decode one token in 9 layers: each row reads and writes its
    32 states of 64 KiB once; the 24 idle slots' states are not counted."""
    pk = peaks.peaks_for("TPU v5 lite")
    f, b = X.gated_delta_step_work(m, 40 * 9)
    assert f == 360 * 3_145_728
    assert b == 360 * 32 * (2 * 128 * 128 + 4 * 128) * 4
    least, bound = flops.roofline_seconds(f, b, pk)
    assert bound == "bandwidth"
    assert least == pytest.approx(1.53e9 / pk.hbm_bw, rel=0.01)
    assert X.gated_delta_step_work(m, 0) == (0, 0)


def test_grouped_matmul_work_counts_three_matrices(m):
    # one decode pass of 64 tokens through the 12 layers, 80 pairs each
    f, b = X.grouped_matmul_work(m, 12 * 80, 12)
    assert f == 2 * 3_145_728 * 960
    assert b == 12 * 64 * 3_145_728 * 2 + 960 * 3 * (2048 + 512) * 2
    assert b == pytest.approx(4.85e9, rel=0.01)     # the issue's ~4.8 GB


def _ctx(gauges, cfg=None):
    win = types.SimpleNamespace(gauges=gauges, turns=[], recs=[],
                                t_start=0.0, t_end=1.0)
    return {"kind": "serve", "win": win, "cfg": cfg or {}, "chunk": 128}


@pytest.mark.parametrize("name", [n for n in NEW if "roofline" in n])
def test_a_roofline_reader_finds_nothing_without_its_kernel(name, m):
    """No trace, or a trace without the kernel's events (the parent commit
    has no ``gated_delta_step``): None, no raise."""
    rd = spec.load_metric_reader(name)
    fn = spec.resolve_reader(rd)
    ctx = _ctx({"moe_tokens": 10, "moe_local_pairs": 10}, {"sizes": m})
    assert fn(rd, ctx) is None
    trace = types.SimpleNamespace(device_ops={"0": []})
    ctx.update(trace=trace, reduced={"window": (0.0, 1.0), "steps": 0},
               span=(0.0, 1.0))
    assert fn(rd, ctx) is None


def test_counter_readers_read_nothing_from_a_program_without_them(m):
    rd = spec.load_metric_reader("step.serve_mfu.qwen3_next")
    assert spec.resolve_reader(rd)(rd, _ctx({}, {"sizes": m})) is None
    rd = spec.load_metric_reader("gdn.chunk_token_share.qwen3_next")
    fn = spec.resolve_reader(rd)
    assert fn(rd, _ctx({"moe_tokens": 5})) is None
    assert fn(rd, _ctx({"gdn_tokens": 0, "gdn_chunk_tokens": 0})) is None
    assert fn(rd, _ctx({"gdn_tokens": 90, "gdn_chunk_tokens": 60})) \
        == pytest.approx(2 / 3)


def test_the_cells_metrics_name_readers_that_resolve(bench):
    cell = spec.find_cell(bench, CELL)
    names = [mt["name"] for mt in spec.cell_metrics(bench, cell, "per_layer")]
    assert set(NEW) | {"moe.local_pairs_per_token.batch",
                       "moe.max_over_mean_load.batch",
                       "step.prefill_fill.batch", "device.idle_share.batch",
                       "sched.slot_occupancy.batch"} <= set(names)
    for other in ("step.serve_mfu.batch", "step.serve_mfu.nemotron_h",
                  "step.serve_mfu.exaone_moe",
                  "kernel.ragged_attn_roofline.exaone_moe",
                  "kernel.grouped_matmul_roofline.exaone_moe"):
        assert other not in names               # other families' counters
    for n in names:
        assert callable(spec.resolve_reader(spec.load_metric_reader(n)))
    # the held experts' count under the name the load metric multiplies by
    cfg = spec.load_config(ROOT, bench, CONFIG)
    assert cfg["sizes"]["n_routed_experts"] == cfg["num_experts"] == 64


def test_the_configuration_file_keeps_the_catalog(bench):
    """Every number of the catalog entry's config under the same key,
    unless the key is in ``reduced`` (then ``published`` keeps it)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    cfg = spec.load_config(ROOT, bench, CONFIG)
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) \
        == set(cfg["published"])
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    for k in ("assumed", "deployment", "memory_reckoning", "check_why"):
        assert cfg[k]


def test_the_traffic_file_is_the_one_the_exaone_cell_runs(bench):
    cells = [w for w in bench["workloads"]
             if w["traffic"] == "mixed_len_closed"]
    assert [w["config"] for w in cells] == ["k-exaone-ep8-d5", CONFIG]
    t = spec.load_traffic("mixed_len_closed")
    assert t["warmup"] == {"requests": 8, "prompt": 300, "output": 18}


def test_closed_loop_serving_through_state_of_a_held_share(bench):
    tr = load_traffic("tiny_closed.json")
    res = drive(bench, load_cfg("tiny-qwen3-next.json"), tr,
                [w["name"] for w in bench["workloads"]].index(CELL))
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["served_gap_max"]["value"] <= 1e-3
