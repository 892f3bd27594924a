"""The EXAONE-MoE cell ``k-exaone-ep8-d5.mixed_len_closed``: its entries
in ``BENCHMARK.json`` against what ISSUE 33 names, the configuration file
against the issue's byte count and the catalog, the work counters of
``harness/exaone_moe.py`` against hand counts (window-limited attention,
three matrices an expert), its readers on a program without the counters or
the kernel, and the cell end to end on the CPU at the tiny size."""

import json
import os
import types

import numpy as np
import pytest

from perfbench.harness import exaone_moe as X
from perfbench.harness import flops, peaks, spec

from conftest import ROOT, drive, load_cfg, load_traffic

CELL = "k-exaone-ep8-d5.mixed_len_closed"
CONFIG = "k-exaone-ep8-d5"


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_the_cell_is_the_one_the_issue_names(bench):
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixed_len_closed", 1)
    assert bench["workloads"][-1] is cell       # added at the end
    assert bench["configs"][-1]["name"] == CONFIG
    e2e = {mt["name"] for mt in spec.cell_metrics(bench, cell, "end_to_end")}
    assert e2e == {"serve_tok_s", "setup_s"}
    for mt in bench["end_to_end"] + bench["per_layer"]:
        if CELL in mt.get("workloads", ()):
            assert mt["workloads"][-1] == CELL  # appended, nothing moved


@pytest.fixture(scope="module")
def m(bench):
    return spec.load_config(ROOT, bench, CONFIG)["sizes"]


def test_the_file_counts_the_parameters_the_issue_counts(m):
    from perfbench.reference import exaone_moe as R
    by = {}
    for name, s, _ in R.param_specs(m):
        l = name.split(".")[1] if name.startswith("layers.") else name
        by[l] = by.get(l, 0) + int(np.prod(s))
    attn = 2 * 6144 * 8192 + 2 * 6144 * 1024 + 2 * 128 + 2 * 6144
    assert attn == 113_258_752                   # 113.25 M + the norms
    assert by["0"] == attn + 3 * 6144 * 18432    # + the dense MLP, 339.74 M
    assert X.expert_params(m) == 3 * 6144 * 2048 == 37_748_736
    assert by["1"] == attn + 17 * 37_748_736 + 6144 * 128 + 128
    assert sum(by.values()) == 3_712_028_416     # 3.712 B = 7.42 GB in bf16
    assert X.expected_local_pairs(m) == 1.0
    assert (X.n_window(m), X.n_global(m), X.n_sparse(m)) == (4, 1, 4)


def test_flops_of_one_token(m):
    assert X.attn_matmul_params(m) == 2 * 6144 * 8192 + 2 * 6144 * 1024
    # sparse: router 6144 x 128, one shared expert, 1.0 local pair
    assert X.sparse_flops_token(m) == 2 * (6144 * 128 + 37_748_736) \
        + 2 * 37_748_736
    assert X.sparse_flops_token(m, 0) == 2 * (6144 * 128 + 37_748_736)
    assert X.layers_flops_token(m) == 5 * 2 * X.attn_matmul_params(m) \
        + 2 * 3 * 6144 * 18432 + 4 * X.sparse_flops_token(m)


def test_a_window_layer_attends_at_most_its_window(m):
    # 10 tokens from position 0: 55 keys either way (under the window)
    assert X.keys_seen(0, 10) == X.keys_seen(0, 10, 128) == 55
    # a decode token at context 1500: 1501 keys, or the window's 128
    assert X.keys_seen(1500, 1) == 1501 and X.keys_seen(1500, 1, 128) == 128
    # a chunk of 128 from position 64: queries 0-63 see 65..128 keys, the
    # other 64 see 128 each
    assert X.keys_seen(64, 128, 128) == sum(range(65, 129)) + 64 * 128
    per_key = 4 * 64 * 128
    assert X.attn_flops(m, 1500, 1) == per_key * (1501 + 4 * 128)
    layer, head = X.layers_flops_token(m), 2 * 6144 * 19200
    got = X.serve_flops(m, [(0, 10)], [None, 11])
    assert got == pytest.approx(10 * layer + per_key * 5 * 55 + head
                                + head + layer + per_key * 5 * 11)


def test_attention_bytes_stop_at_the_window(m):
    """One decode call at a context of 1500: the global layer reads 1501
    keys' K and V, each of the four window layers 128; a chunk of 128 on a
    history of 1000 reads 1128 against 255."""
    kv = 2 * 8 * 128 * 2                      # K and V of one key, bf16
    qo = 2 * 64 * 128 * 2                     # q in and out, one token
    f, b = X.ragged_attention_work(m, [(1500, 1), (7, 0)], kv_bytes=2)
    assert f == 4 * 64 * 128 * (1501 + 4 * 128)
    assert b == kv * (1501 + 4 * 128) + 5 * qo
    _, b = X.ragged_attention_work(m, [(1000, 128)], kv_bytes=2)
    assert b == kv * (1128 + 4 * 255) + 5 * 128 * qo
    # under the window both kinds read the same
    _, b = X.ragged_attention_work(m, [(0, 100)], kv_bytes=2)
    assert b == kv * 5 * 100 + 5 * 100 * qo


def test_grouped_matmul_work_counts_three_matrices(m):
    pk = peaks.peaks_for("TPU v5 lite")
    # one decode pass of 64 tokens through the 4 sparse layers, 64 pairs each
    f, b = X.grouped_matmul_work(m, 4 * 64, 4)
    assert f == 2 * 37_748_736 * 256
    assert b == 4 * 16 * 37_748_736 * 2 + 256 * 3 * (6144 + 2048) * 2
    least, bound = flops.roofline_seconds(f, b, pk)
    assert bound == "bandwidth"                  # 4.83 GB of experts a pass
    assert least == pytest.approx(4.84e9 / pk.hbm_bw, rel=5e-3)


def _ctx(gauges, cfg=None):
    win = types.SimpleNamespace(gauges=gauges, turns=[], recs=[],
                                t_start=0.0, t_end=1.0)
    return {"kind": "serve", "win": win, "cfg": cfg or {}, "chunk": 128}


@pytest.mark.parametrize("name", ["kernel.grouped_matmul_roofline.exaone_moe",
                                  "kernel.ragged_attn_roofline.exaone_moe"])
def test_a_roofline_reader_finds_nothing_without_its_kernel(name, m):
    """No trace, or a trace without the kernel's events: None, no raise."""
    rd = spec.load_metric_reader(name)
    fn = spec.resolve_reader(rd)
    ctx = _ctx({"moe_tokens": 10, "moe_local_pairs": 10}, {"sizes": m})
    assert fn(rd, ctx) is None
    trace = types.SimpleNamespace(device_ops={"0": []})
    ctx.update(trace=trace, reduced={"window": (0.0, 1.0), "steps": 0},
               span=(0.0, 1.0))
    assert fn(rd, ctx) is None


def test_serve_mfu_reader_reads_nothing_from_an_empty_window(m):
    rd = spec.load_metric_reader("step.serve_mfu.exaone_moe")
    assert spec.resolve_reader(rd)(rd, _ctx({}, {"sizes": m})) is None


def test_the_cells_metrics_name_readers_that_resolve(bench):
    cell = spec.find_cell(bench, CELL)
    names = [mt["name"] for mt in spec.cell_metrics(bench, cell, "per_layer")]
    assert {"step.serve_mfu.exaone_moe",
            "kernel.grouped_matmul_roofline.exaone_moe",
            "kernel.ragged_attn_roofline.exaone_moe",
            "moe.local_pairs_per_token.batch",
            "moe.max_over_mean_load.batch", "step.prefill_fill.batch",
            "device.idle_share.batch"} <= set(names)
    for other in ("step.serve_mfu.batch", "step.serve_mfu.nemotron_h",
                  "kernel.ragged_attn_roofline.batch",
                  "kernel.grouped_matmul_roofline.batch"):
        assert other not in names               # other families' counters
    for n in names:
        assert callable(spec.resolve_reader(spec.load_metric_reader(n)))
    # the held experts' count under the name the load metric multiplies by
    cfg = spec.load_config(ROOT, bench, CONFIG)
    assert cfg["sizes"]["n_routed_experts"] == cfg["num_experts"] == 16


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
    for k in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert cfg[k] == row["config"][k][:5]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])


def test_the_traffic_file_is_the_issues_table():
    from perfbench.harness import traffic
    t = spec.load_traffic("mixed_len_closed")
    assert (t["kind"], t["clients"], t["pool"]) == ("closed", 96, 96)
    p, o = traffic.lengths(t["prompt"], 96), traffic.lengths(t["output"], 96)
    assert (p.min(), p.max(), int(np.median(p))) == (79, 4096, 1020)
    assert (p == 4096).sum() == 8 and round(p.mean()) == 1433
    assert (o.min(), o.max(), round(o.mean())) == (17, 1001, 176)
    assert t["warmup"] == {"requests": 8, "prompt": 300, "output": 18}
    assert t["trace_seconds"] == 4


def test_closed_loop_serving_through_rings_of_a_held_share(bench):
    tr = load_traffic("tiny_closed.json")
    res = drive(bench, load_cfg("tiny-exaone-moe.json"), tr,
                [w["name"] for w in bench["workloads"]].index(CELL))
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["served_gap_max"]["value"] <= 1e-3
