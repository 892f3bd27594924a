"""The hybrid decoder's work counters against values worked by hand, its
readers on synthetic windows, and its cell rehearsed end to end on the CPU
at a tiny size (one holder of a quarter of the experts)."""

import json
import os
import types

import numpy as np

import pytest

from perfbench.harness import flops, hybrid, peaks, spec

from conftest import ROOT, drive, load_cfg, load_traffic

CELL = "nemotron3-super-ep4-d11.batch_closed"


@pytest.fixture(scope="module")
def m(bench):
    return spec.load_config(ROOT, bench, "nemotron3-super-ep4-d11")["sizes"]


def test_the_file_counts_the_parameters_the_issue_counts(m):
    from perfbench.reference import nemotron_h as R
    n = sum(int(np.prod(s)) for _, s, _ in R.param_specs(m))
    assert n == 4_648_163_712                    # 4.648 B = 9.30 GB in bf16
    by = {}
    for name, s, _ in R.param_specs(m):
        l = name.split(".")[1] if name.startswith("layers.") else name
        by[l] = by.get(l, 0) + int(np.prod(s))
    assert by["0"] == 109_640_064                # one M layer: 109.64 M
    assert by["7"] == 35_655_680                 # the * layer: 35.66 M
    assert by["1"] == 54_530_560 + 128 * 5_505_024   # E: 54.53 M + experts
    assert hybrid.expert_params(m) == 2 * 1024 * 2688 == 5_505_024
    assert hybrid.expected_local_pairs(m) == 5.5


def test_flops_of_one_token(m):
    # M: in 4096 x 18560, out 8192 x 4096; conv 4 x 10240; recurrence
    # 5 x 128 x 64 x 128 + 2 x 128 x 64
    assert hybrid.mamba_flops_token(m) == 2 * (4096 * 18560 + 8192 * 4096) \
        + 2 * 4 * 10240 + 5 * 1_048_576 + 2 * 8192
    assert hybrid.attn_matmul_params(m) == 2 * 4096 * 4096 + 2 * 4096 * 256
    # E: router 4096 x 512, latent 2 x 4096 x 1024, shared 2 x 4096 x 5376,
    # and 5.5 local pairs of 5.505 M
    assert hybrid.moe_flops_token(m) == 2 * (4096 * 512 + 2 * 4096 * 1024
                                             + 2 * 4096 * 5376) \
        + 2 * 5_505_024 * 5.5
    assert hybrid.moe_flops_token(m, 0) == 2 * 54_525_952
    assert hybrid.layers_flops_token(m) == 5 * hybrid.mamba_flops_token(m) \
        + 2 * hybrid.attn_matmul_params(m) + 5 * hybrid.moe_flops_token(m)
    # one attention layer: a 10-token prompt sees 55 keys, 4 x 32 x 128 each
    assert hybrid.attn_flops(m, 0, 10) == 4 * 55 * 4096


def test_serve_flops_counts_the_head_once_per_sampled_token(m):
    layer, head = hybrid.layers_flops_token(m), 2 * 4096 * 32768
    got = hybrid.serve_flops(m, [(0, 10)], [None, 11])
    want = 10 * layer + 4 * 55 * 4096 + head \
        + (head + layer + 4 * 11 * 4096)
    assert got == pytest.approx(want)


def test_grouped_matmul_work_and_its_bound(m):
    pk = peaks.peaks_for("TPU v5 lite")
    # one decode pass of 64 tokens through the 5 E layers: 352 pairs a layer
    f, b = hybrid.grouped_matmul_work(m, 5 * 352, 5)
    assert f == 2 * 5_505_024 * 1760
    assert b == 5 * 128 * 5_505_024 * 2 + 1760 * 2 * (1024 + 2688) * 2
    from perfbench.harness import flops
    least, bound = flops.roofline_seconds(f, b, pk)
    assert bound == "bandwidth"                  # 7.05 GB of experts a pass
    assert least == pytest.approx(7.07e9 / pk.hbm_bw, rel=5e-3)


def _ctx(gauges, cfg=None):
    win = types.SimpleNamespace(gauges=gauges)
    return {"kind": "serve", "win": win, "cfg": cfg or {}}


def test_gauge_ratio_reader(m):
    g = {"moe_tokens": 1000, "moe_local_pairs": 5400,
         "moe_max_expert_pairs": 90}
    rd = spec.load_metric_reader("moe.local_pairs_per_token.batch")
    assert spec.resolve_reader(rd)(rd, _ctx(g)) == 5.4
    rd = spec.load_metric_reader("moe.max_over_mean_load.batch")
    got = spec.resolve_reader(rd)(rd, _ctx(g, {"sizes": m}))
    assert got == pytest.approx(90 / (5400 / 128))
    # a program without the counters (the parent) is not read
    for name in ("moe.local_pairs_per_token.batch",
                 "moe.max_over_mean_load.batch"):
        rd = spec.load_metric_reader(name)
        assert spec.resolve_reader(rd)(rd, _ctx({"tokens_emitted": 5})) \
            is None


def test_attention_work_counts_the_star_layers_alone(m):
    """One layer in eleven attends: the dense counter's work (one
    attention per layer of num_hidden_layers) x 1/11, and by hand for one
    decode call of one query on 99 keys of history."""
    calls = [(99, 1), (0, 128), (5, 0)]
    f, b = hybrid.ragged_attention_work(m, calls, kv_bytes=2)
    f_all, b_all = flops.ragged_attention_work(m, calls, kv_bytes=2)
    assert f == pytest.approx(f_all / 11) and b == pytest.approx(b_all / 11)
    f1, b1 = hybrid.ragged_attention_work(m, [(99, 1)], kv_bytes=2)
    assert f1 == pytest.approx(4 * 100 * 32 * 128)
    assert b1 == pytest.approx(2 * 100 * 2 * 128 * 2 + 2 * 32 * 128 * 2)


def test_the_kernel_patterns_tell_the_kernels_apart():
    """`grouped_matmul`'s pattern reads the forward kernel's events,
    first instance and numbered ones, and not the dw kernel's."""
    import re
    rx = re.compile(spec.load_metric_reader(
        "kernel.grouped_matmul_roofline.batch")["pattern"])
    assert rx.search("%grouped_matmul = bf16[3472,1024]{1,0} custom-call(")
    assert rx.search("%grouped_matmul.17 = bf16[3472,2688]{1,0} custom-call(")
    assert not rx.search("%grouped_matmul_dw.2 = bf16[128,1024,2688]{2,1,0} "
                         "custom-call(")


def test_passes_per_turn_from_the_engines_counts():
    cfg = {"engine": {"num_slots": 64}}
    g = {"tokens_emitted": 8000, "slot_occupancy": 8000 / (64 * 16 * 10),
         "unified_steps": 10}
    assert hybrid._passes_per_turn(cfg, g) == pytest.approx(16)
    assert hybrid._passes_per_turn(cfg, {"unified_steps": 0}) is None


def test_new_metrics_name_readers_that_resolve(bench):
    cell = spec.find_cell(bench, CELL)
    names = [mt["name"] for mt in spec.cell_metrics(bench, cell, "per_layer")]
    assert {"step.serve_mfu.nemotron_h", "kernel.grouped_matmul_roofline.batch",
            "moe.local_pairs_per_token.batch",
            "moe.max_over_mean_load.batch",
            "kernel.ragged_attn_roofline.nemotron_h"} <= set(names)
    assert "step.serve_mfu.batch" not in names       # flops.py counts a
    assert "kernel.ragged_attn_roofline.batch" not in names   # dense decoder
    for n in names:
        assert callable(spec.resolve_reader(spec.load_metric_reader(n)))


def test_the_configuration_file_keeps_the_catalog(bench):
    """Every number of the catalog entry's config under the same key,
    unless the key is in ``reduced`` (then ``published`` keeps it)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    cfg = spec.load_config(ROOT, bench, "nemotron3-super-ep4-d11")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == cfg["source"])
    for k, v in row["config"].items():
        if k in cfg["reduced"]:
            assert cfg["published"][k] == v
        else:
            assert cfg[k] == v, k
    assert cfg["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"][:11]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])


def test_closed_loop_serving_of_a_held_share(bench):
    tr = load_traffic("tiny_closed.json")
    res = drive(bench, load_cfg("tiny-nemotron-h.json"), tr,
                [w["name"] for w in bench["workloads"]].index(CELL))
    assert set(res["metrics"]) == {"serve_tok_s", "setup_s"}
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["served_gap_max"]["value"] <= 1e-3
