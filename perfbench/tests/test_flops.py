"""The work counters against values worked by hand."""

import pytest

from perfbench.harness import flops, peaks

QWEN = {"vocab_size": 152064, "hidden_size": 3584, "intermediate_size": 18944,
        "num_hidden_layers": 8, "num_attention_heads": 28,
        "num_key_value_heads": 4, "mlp_gated": True}
GPT2 = {"vocab_size": 50257, "hidden_size": 768, "intermediate_size": 3072,
        "num_hidden_layers": 12, "num_attention_heads": 12,
        "num_key_value_heads": 12, "mlp_gated": False}


def test_one_qwen2_layer():
    # q, o: 3584 x 3584 each; k, v: 3584 x 512 each; MLP 3 x 3584 x 18944
    want = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert want == 233_046_016
    assert flops.layer_matmul_params(QWEN) == want
    assert flops.head_matmul_params(QWEN) == 3584 * 152064
    # one decode token over 1000 keys: 4 x 1000 x 28 x 128 per layer
    assert flops.attn_flops_token(dict(QWEN, num_hidden_layers=1), 1000) \
        == 14_336_000


def test_gpt2_step():
    # per layer 768x2304 + 768x768 + 2 x 768x3072 = 7,077,888; head 38,597,376
    assert flops.layer_matmul_params(GPT2) == 7_077_888
    assert flops.matmul_params(GPT2) == 12 * 7_077_888 + 38_597_376 \
        == 123_532_032
    # causal attention at seq 1024: 4 x (1024 x 1025 / 2) x 768 x 12 layers
    assert flops.attn_flops_causal(GPT2, 1024) == 4 * 524_800 * 768 * 12
    per_tok = flops.train_flops_per_token(GPT2, 1024)
    assert per_tok == pytest.approx(6 * 123_532_032
                                    + 3 * 4 * 524_800 * 768 * 12 / 1024)
    assert per_tok == pytest.approx(0.798e9, rel=2e-3)   # ~0.80 GFLOP/token


def test_span_sums_to_the_causal_whole():
    whole = flops.attn_flops_causal(QWEN, 300)
    parts = sum(flops.attn_flops_span(QWEN, s, n)
                for s, n in [(0, 128), (128, 128), (256, 44)])
    assert parts == pytest.approx(whole)


def test_serve_flops_counts_the_head_once_per_sampled_token():
    m = dict(QWEN, num_hidden_layers=1)
    layer, head = 2 * 233_046_016, 2 * 3584 * 152064
    # a 10-token prompt, its first token, then one decode token over 11 keys
    got = flops.serve_flops(m, [(0, 10)], [None, 11])
    want = 10 * layer + 4 * 55 * 3584 + head + (head + layer + 4 * 11 * 3584)
    assert got == pytest.approx(want)


def test_ragged_and_flash_work_and_roofline():
    pk = peaks.peaks_for("TPU v5 lite")
    m = dict(QWEN, num_hidden_layers=1)
    f, b = flops.ragged_attention_work(m, [(1000, 1), (0, 0)], kv_bytes=2)
    assert f == 4 * 1001 * 3584
    assert b == 2 * 1001 * 512 * 2 + 2 * 1 * 3584 * 2
    t, bound = flops.roofline_seconds(f, b, pk)
    assert bound == "bandwidth" and t == pytest.approx(b / 819e9)
    f, b = flops.flash_attention_work(dict(GPT2, num_hidden_layers=1), 4, 1024)
    assert f == 3 * 4 * 524_800 * 768 * 4
    assert b == (2 + 4) * 2 * (4 * 1024 * 768 * 2)   # MHA: q and kv alike
    t, bound = flops.roofline_seconds(f, b, pk)
    assert bound == "compute"
