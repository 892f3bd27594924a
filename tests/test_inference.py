"""Inference engine: Config/Predictor API, export round-trip, paged
attention.

Oracles (SURVEY.md §4 "Inference tests"): predictor numeric parity vs
the eager layer, class-free execution from the serialized export, and
paged attention vs a dense-attention oracle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference import (Config, PrecisionType, create_predictor)
from paddle_tpu.ops.paged_attention import (kv_pool_shape, paged_attention,
                                            paged_attention_reference)
from paddle_tpu.ops.pallas.flash_attention import flash_attention_reference


class SmallNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


@pytest.fixture
def saved_model(tmp_path):
    paddle.seed(7)
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "net")
    paddle.jit.save(net, path,
                    input_spec=[paddle.static.InputSpec([2, 8], "float32")])
    x = np.random.RandomState(0).randn(2, 8).astype("float32")
    ref = net(paddle.to_tensor(x)).numpy()
    return path, x, ref


def test_predictor_from_export(saved_model):
    """Class-free execution: Config(prog_file) -> handles -> run."""
    path, x, ref = saved_model
    cfg = Config(path + ".pdmodel")
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    names = pred.get_input_names()
    assert len(names) == 1
    h = pred.get_input_handle(names[0])
    h.copy_from_cpu(x)
    assert pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0])
    np.testing.assert_allclose(out.copy_to_cpu(), ref,
                               rtol=1e-5, atol=1e-6)
    assert out.shape() == [2, 4]


def test_predictor_run_convenience(saved_model):
    path, x, ref = saved_model
    cfg = Config(path + ".pdmodel")
    outs = create_predictor(cfg).run([x])
    np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-6)


def test_predictor_clone_shares_program(saved_model):
    path, x, ref = saved_model
    pred = create_predictor(Config(path + ".pdmodel"))
    clone = pred.clone()
    assert clone._fn is pred._fn
    np.testing.assert_allclose(clone.run([x])[0], ref,
                               rtol=1e-5, atol=1e-6)


def test_predictor_from_layer(saved_model):
    """In-memory layer serving path."""
    path, x, ref = saved_model
    paddle.seed(7)
    net = SmallNet()
    net.set_state_dict(paddle.load(path + ".pdiparams"))
    cfg = Config()
    cfg.set_layer(net)
    outs = create_predictor(cfg).run([x])
    np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-6)


def test_jit_load_without_class(saved_model, tmp_path):
    """paddle.jit.load with no layer runs via the serialized export."""
    path, x, ref = saved_model
    loaded = paddle.jit.load(path)
    out = loaded(paddle.to_tensor(x))
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-6)


def test_dynamic_batch_export(tmp_path):
    """InputSpec dims of -1 export symbolically: the class-free artifact
    serves any batch size."""
    paddle.seed(11)
    net = SmallNet()
    net.eval()
    path = str(tmp_path / "dyn")
    paddle.jit.save(net, path,
                    input_spec=[paddle.static.InputSpec([-1, 8],
                                                        "float32")])
    pred = create_predictor(Config(path + ".pdmodel"))
    for bs in (1, 4, 7):
        x = np.random.RandomState(bs).randn(bs, 8).astype("float32")
        ref = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(pred.run([x])[0], ref,
                                   rtol=1e-5, atol=1e-6)


def test_config_api_surface(tmp_path):
    d = str(tmp_path / "dir_model")
    import os
    os.makedirs(d)
    cfg = Config(d)
    assert cfg.model_dir() == d
    cfg2 = Config("m.pdmodel", "m.pdiparams")
    assert cfg2.prog_file() == "m.pdmodel"
    cfg2.enable_use_gpu(100, 0, PrecisionType.Bfloat16)
    assert cfg2.use_gpu()
    cfg2.switch_ir_optim(False)
    assert not cfg2.ir_optim()
    cfg2.enable_memory_optim()
    assert cfg2.memory_optim_enabled()
    assert not cfg2.tensorrt_engine_enabled()
    assert "precision" in cfg2.summary()


# --------------------------------------------------------------------------
# paged attention
# --------------------------------------------------------------------------

def _build_paged_case(rng, B, H, KVH, D, page, n_pages_per_seq,
                      total_pages, lens):
    """Scatter dense K/V into a shuffled page pool; return both views."""
    max_len = page * n_pages_per_seq
    k_dense = rng.randn(B, max_len, KVH, D).astype("float32")
    v_dense = rng.randn(B, max_len, KVH, D).astype("float32")
    # the pool's one layout: [pages, page, KVH * D], a token = one row
    key_pages = np.zeros(kv_pool_shape(KVH, total_pages, page, D),
                         "float32")
    value_pages = np.zeros_like(key_pages)
    perm = rng.permutation(total_pages)
    tables = np.zeros((B, n_pages_per_seq), "int32")
    pid = 0
    for b in range(B):
        for j in range(n_pages_per_seq):
            pg = perm[pid]
            pid += 1
            tables[b, j] = pg
            sl = slice(j * page, (j + 1) * page)
            key_pages[pg] = k_dense[b, sl].reshape(page, KVH * D)
            value_pages[pg] = v_dense[b, sl].reshape(page, KVH * D)
    return k_dense, v_dense, key_pages, value_pages, tables


@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_paged_attention_vs_dense(H, KVH):
    """Paged gather path == dense attention over the valid prefix."""
    rng = np.random.RandomState(0)
    B, D, page, npps = 3, 16, 8, 4
    total = B * npps + 2
    lens = np.array([5, 17, 32], "int32")
    k_dense, v_dense, kp, vp, tables = _build_paged_case(
        rng, B, H, KVH, D, page, npps, total, lens)
    q = rng.randn(B, H, D).astype("float32")

    out = paged_attention(jnp.asarray(q), jnp.asarray(kp),
                          jnp.asarray(vp), jnp.asarray(tables),
                          jnp.asarray(lens))

    # dense oracle per sequence over its valid prefix, GQA-expanded
    rep = H // KVH
    for b in range(B):
        L = int(lens[b])
        k = np.repeat(k_dense[b, :L], rep, axis=1)  # [L, H, D]
        v = np.repeat(v_dense[b, :L], rep, axis=1)
        ref = flash_attention_reference(
            jnp.asarray(q[b][None, None]),           # [1, 1, H, D]
            jnp.asarray(k[None]), jnp.asarray(v[None]))
        np.testing.assert_allclose(np.asarray(out[b]),
                                   np.asarray(ref[0, 0]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2)])
def test_paged_prefill_attention_vs_dense_causal(H, KVH):
    """Chunked-prefill oracle (ISSUE 3): C query tokens over paged
    history + causal-within-chunk == dense causal attention over the
    prefix, per query position."""
    from paddle_tpu.ops.paged_attention import (
        paged_prefill_attention, paged_prefill_attention_reference)
    rng = np.random.RandomState(2)
    B, D, page, npps, C = 3, 16, 8, 4, 5
    total = B * npps + 2
    # ctx BEFORE the chunk; chunk tokens live at ctx..ctx+C-1 and are
    # already in the pages (the dense view holds them too)
    ctx = np.array([0, 7, 19], "int32")
    k_dense, v_dense, kp, vp, tables = _build_paged_case(
        rng, B, H, KVH, D, page, npps, total, ctx + C)
    q = rng.randn(B, C, H, D).astype("float32")

    out = paged_prefill_attention(jnp.asarray(q), jnp.asarray(kp),
                                  jnp.asarray(vp), jnp.asarray(tables),
                                  jnp.asarray(ctx))
    assert np.asarray(out).shape == (B, C, H, D)
    rep = H // KVH
    scale = 1.0 / np.sqrt(D)
    for b in range(B):
        for j in range(C):
            L = int(ctx[b]) + j + 1       # causal: positions <= ctx+j
            k = np.repeat(k_dense[b, :L], rep, axis=1)   # [L, H, D]
            v = np.repeat(v_dense[b, :L], rep, axis=1)
            logits = np.einsum("hd,lhd->hl", q[b, j], k) * scale
            w = np.exp(logits - logits.max(-1, keepdims=True))
            w = w / w.sum(-1, keepdims=True)
            ref = np.einsum("hl,lhd->hd", w, v)
            np.testing.assert_allclose(np.asarray(out[b, j]), ref,
                                       rtol=2e-5, atol=2e-5)
    # C == 1 reduces exactly to the decode oracle at ctx+1
    out1 = paged_prefill_attention_reference(
        jnp.asarray(q[:, :1]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx))
    dec = paged_attention_reference(
        jnp.asarray(q[:, 0]), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(ctx + 1))
    np.testing.assert_allclose(np.asarray(out1[:, 0]), np.asarray(dec),
                               rtol=1e-6, atol=1e-6)


def test_paged_prefill_write_routes_and_trashes():
    """Chunk writes land at ctx..ctx+valid-1 in the slot's pages; tokens
    past the valid count (chunk padding / slots outside the wave) go to
    the reserved trash page 0 and clobber nothing real."""
    from paddle_tpu.ops.paged_attention import paged_prefill_write
    rng = np.random.RandomState(3)
    KVH, D, page, npps, B, C = 2, 4, 4, 3, 2, 5
    total = 1 + B * npps                   # page 0 = trash
    kp = np.zeros(kv_pool_shape(KVH, total, page, D), "float32")
    vp = np.zeros_like(kp)
    tables = np.arange(1, 1 + B * npps,
                       dtype="int32").reshape(B, npps)
    k = rng.randn(B, C, KVH, D).astype("float32")
    v = rng.randn(B, C, KVH, D).astype("float32")
    ctx = np.array([2, 6], "int32")
    valid = np.array([5, 3], "int32")      # slot 1: 2 padding tokens
    kp2, vp2 = paged_prefill_write(
        jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(k),
        jnp.asarray(v), jnp.asarray(tables), jnp.asarray(ctx),
        jnp.asarray(valid))
    kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
    for b in range(B):
        for j in range(int(valid[b])):
            pos = int(ctx[b]) + j
            pg, off = tables[b, pos // page], pos % page
            np.testing.assert_array_equal(kp2[pg, off],
                                          k[b, j].reshape(-1))
            np.testing.assert_array_equal(vp2[pg, off],
                                          v[b, j].reshape(-1))
    # nothing outside the written positions changed (trash page aside)
    mask = np.ones((total,), bool)
    written = {int(tables[b, (int(ctx[b]) + j) // page])
               for b in range(B) for j in range(int(valid[b]))}
    for pg in range(1, total):
        if pg not in written:
            assert not kp2[pg].any() and not vp2[pg].any()
    assert mask[0]                          # page 0 absorbed the padding


@pytest.mark.parametrize("H,KVH", [(4, 4), (8, 2), (14, 2)])
def test_paged_attention_is_the_ragged_entry_at_length_one(H, KVH):
    """``paged_attention`` has no kernel of its own: it is
    ``ragged_paged_attention`` with one query token per sequence, equal
    to the decode reference on the jnp path and through the Pallas
    kernel (interpret mode)."""
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention as kernel)
    rng = np.random.RandomState(4)
    B, D, page, npps = 3, 16, 8, 4
    lens = np.array([1, 16, 27], "int32")    # 16: a page's last offset
    _, _, kp, vp, tables = _build_paged_case(
        rng, B, H, KVH, D, page, npps, B * npps + 2, lens)
    q = rng.randn(B, H, D).astype("float32")
    args = [jnp.asarray(a) for a in (kp, vp, tables)]
    dec = np.asarray(paged_attention_reference(
        jnp.asarray(q), *args, jnp.asarray(lens)))
    out = paged_attention(jnp.asarray(q), *args, jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), dec, rtol=1e-6, atol=1e-6)
    out_k = kernel(jnp.asarray(q[:, None]), *args, jnp.asarray(lens - 1),
                   jnp.ones((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(out_k[:, 0]), dec,
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_incubate_api():
    rng = np.random.RandomState(1)
    B, H, KVH, D, page, npps = 2, 4, 4, 8, 4, 2
    lens = np.array([3, 8], "int32")
    _, _, kp, vp, tables = _build_paged_case(
        rng, B, H, KVH, D, page, npps, B * npps, lens)
    q = rng.randn(B, H, D).astype("float32")
    from paddle_tpu.incubate.nn.functional import paged_attention as pa
    out = pa(paddle.to_tensor(q), paddle.to_tensor(kp),
             paddle.to_tensor(vp), paddle.to_tensor(tables),
             paddle.to_tensor(lens))
    ref = paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lens))
    np.testing.assert_allclose(out.numpy(), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
