"""MiniCPM-SALA (InfLLM-v2 block-sparse attention over pages with a
compressed-key index, beside decayed linear-attention layers) against its
plain reference (perfbench/reference/minicpm_sala.py) at the tiny preset,
seeded float32 weights: the whole-sequence forward, prefill in chunks whose
boundaries fall inside a block and inside a compressed entry then decode
through the caches, the selection against the reference's chosen blocks
(chunked, across the dense length, over a reused slot's stale index), the
engine end to end with its counters, the lightning rule's block and
one-step forms, the cache spec and what the engine refuses, and the
parameter count at the published widths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.framework.core import Tensor  # noqa: E402
from paddle_tpu.inference import ContinuousBatchingEngine  # noqa: E402
from paddle_tpu.inference.cache_spec import (CompressedKeys,  # noqa: E402
                                             PagedKV, SlotState,
                                             StepCounters)
from paddle_tpu.models import (MiniCpmSalaConfig,  # noqa: E402
                               MiniCpmSalaForCausalLM)
from paddle_tpu.models.minicpm_sala import COUNTERS, decays  # noqa: E402
from paddle_tpu.ops import gated_delta as gd  # noqa: E402
from paddle_tpu.ops import paged_attention as PA  # noqa: E402
from paddle_tpu.ops import sparse_select as SS  # noqa: E402
from paddle_tpu.ops.mamba2 import ssd_chunked  # noqa: E402
from perfbench.harness import weights  # noqa: E402
from perfbench.reference import minicpm_sala as R  # noqa: E402

STD = 0.05          # wider than 0.02: at 64 wide the logits would be flat
# float32 both sides: sums in another order (the block form against the
# reference's unrolled sum, pages against one array)
TOL = 1e-4

_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "mixer_types", "rms_norm_eps",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "lightning_nh", "lightning_head_dim", "rope_theta", "scale_emb",
         "scale_depth", "dim_model_base", "first_published_layer",
         "sparse_kernel_size", "sparse_kernel_stride", "sparse_block_size",
         "sparse_topk", "sparse_init_blocks", "sparse_window_size",
         "sparse_dense_len")


def sizes(cfg):
    m = {k: getattr(cfg, k) for k in _KEYS}
    m["published"] = {"num_hidden_layers": cfg.published_num_hidden_layers}
    return m


def seeded(cfg, seed):
    m = sizes(cfg)
    specs = R.param_specs(m)
    paddle.seed(0)
    model = MiniCpmSalaForCausalLM(cfg)
    model.eval()
    named = list(model.named_parameters())
    assert [(n, tuple(p.shape)) for n, p in named] \
        == [(n, tuple(s)) for n, s, _ in specs]
    for (_, p), a in zip(named, weights.make_all(specs, seed, STD,
                                                 jnp.float32)):
        p.set_data(a)
    src = weights.LeafSource(specs, weights.seed_words(seed), STD,
                             jnp.float32, R.LAYER_PATTERN)
    return model, m, src


@pytest.fixture(scope="module")
def tiny():
    return seeded(MiniCpmSalaConfig.tiny(), 21)


def _ref_logits(m, src, ids):
    return np.asarray(R.logits(m, src, jnp.asarray(ids)))


# ---- the presets, the parameters ---------------------------------------------

def test_the_presets_follow_the_published_pattern():
    big = MiniCpmSalaConfig.minicpm_sala_9b()
    assert big.kinds.count("minicpm4") == 8 and len(big.kinds) == 32
    assert big.kinds[12:20] == ("lightning-attn",) * 4 + ("minicpm4",) * 2 \
        + ("lightning-attn",) * 2
    assert big.alpha == pytest.approx(1.4 / 32 ** 0.5)
    r = big.rule
    assert (r.kernel, r.stride, r.block, r.topk, r.init, r.window,
            r.dense_len) == (32, 16, 64, 64, 1, 2048, 8192)
    # the slope rule over the PUBLISHED index: layer 12 of 32, head 0 / 31
    s = np.asarray(decays(MiniCpmSalaConfig(first_published_layer=12), 0))
    assert s[0] == pytest.approx(2 ** -0.25 * (1 - 12 / 31 + 1e-5))
    assert s[31] == pytest.approx(2 ** -8 * (1 - 12 / 31 + 1e-5))


def test_the_parameter_count_at_the_published_widths():
    """The benchmark's cut (published layers 12-19) built without storage:
    2,820,569,088 parameters, 5.64 GB in bfloat16."""
    cfg = MiniCpmSalaConfig(num_hidden_layers=8, first_published_layer=12,
                            dtype="bfloat16", empty_init=True)
    cfg.mixer_types = MiniCpmSalaConfig().mixer_types[12:20]
    model = MiniCpmSalaForCausalLM(cfg)
    assert sum(int(np.prod(p.shape)) for p in model.parameters()) \
        == 2_820_569_088


def test_what_the_program_does_not_build_is_refused_by_name():
    for k, v in (("attn_use_rope", True), ("lightning_use_rope", False),
                 ("use_output_norm", False), ("lightning_nkv", 2)):
        cfg = MiniCpmSalaConfig.tiny()
        setattr(cfg, k, v)
        with pytest.raises(ValueError):
            MiniCpmSalaForCausalLM(cfg)
    cfg = MiniCpmSalaConfig.tiny()
    cfg.sparse_topk = 3              # cannot hold the window's blocks
    with pytest.raises(ValueError, match="window"):
        MiniCpmSalaForCausalLM(cfg)


# ---- the forward against the reference -----------------------------------------

def test_whole_sequence_forward_is_the_reference(tiny):
    """80 positions: past the window (16), the dense length (48) and the
    block the window does not hold, so queries select."""
    model, m, src = tiny
    ids = np.random.default_rng(0).integers(0, 128, (2, 80)).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._data)
    np.testing.assert_allclose(got, _ref_logits(m, src, ids), atol=TOL)


def _drive(model, ids, chunk, perm_seed=0):
    """Prefill ``ids[:-n_dec]`` in chunks of ``chunk`` through the caches
    (pages of the slot scattered through the pool), then decode one token
    at a time: every position's logits."""
    c = model.config
    T = ids.shape[1]
    caches, _ = model._fresh_caches(1, T)
    mp = -(-T // c.sparse_block_size)
    tbl = Tensor(jnp.asarray(
        1 + np.random.default_rng(perm_seed).permutation(mp)[None],
        jnp.int32))
    out, ctx = [], 0
    n_pre = T - 6
    while ctx < T:
        n = min(chunk, n_pre - ctx) if ctx < n_pre else 1
        part = np.zeros((1, n if ctx >= n_pre else chunk), np.int32)
        part[0, :n] = ids[0, ctx:ctx + n]
        gate = jnp.asarray([n], jnp.int32) if ctx < n_pre \
            else jnp.asarray([True])
        with paddle.no_grad():
            lg, caches = model(paddle.to_tensor(part), caches=caches,
                               pos=Tensor(jnp.asarray([[ctx]], jnp.int32)),
                               tables=(tbl, Tensor(gate)))
        out.append(np.asarray(lg._data)[0, :n])
        ctx += n
    return np.concatenate(out), caches


@pytest.mark.parametrize("chunk", [12, 5])
def test_prefill_in_chunks_then_decode_is_the_reference(tiny, chunk):
    """Chunk boundaries inside a block (8) and inside a compressed entry's
    8 keys (stride 4); the prompt crosses the dense length, the decode
    steps take the one-step lightning rule: every position's logits within
    1e-4 of the reference's whole-sequence forward."""
    model, m, src = tiny
    ids = np.random.default_rng(chunk).integers(0, 128, (1, 77)).astype(
        np.int32)
    got, caches = _drive(model, ids, chunk)
    np.testing.assert_allclose(got, _ref_logits(m, src, ids)[0], atol=TOL)
    counts = dict(zip(COUNTERS, np.asarray(caches[-1]._data)))
    # 2 sparse layers x 2 KV groups; queries at p >= 48 are sparse
    assert counts["sparse_queries"] == 2 * 2 * (77 - 48)
    assert counts["dense_queries"] == 2 * 2 * 48
    assert counts["sparse_blocks_selected"] == counts["sparse_queries"] * 5
    # off the TPU the oracle gathers the slot's whole table (10 pages)
    assert counts["sparse_blocks_loaded"] == counts["sparse_queries"] * 10


# ---- the selection against the reference's ------------------------------------

@pytest.mark.parametrize("chunk", [12, 5, 77])
def test_the_selection_is_the_references(tiny, chunk):
    """Keys written chunk by chunk into scattered pages, the compressed
    index completed as they arrive (over a reused slot's stale rows), then
    every query's blocks: the reference's chosen set, query by query, KV
    group by KV group."""
    _, m, _ = tiny
    rule = MiniCpmSalaConfig.tiny().rule
    rng = np.random.default_rng(chunk)
    T, G, R_, D = 77, 2, 2, 16
    k = rng.normal(size=(T, G, D)).astype(np.float32)
    q = rng.normal(size=(T, G * R_, D)).astype(np.float32)
    mp = -(-T // rule.block)
    tbl = jnp.asarray(1 + rng.permutation(mp)[None], jnp.int32)
    kp = jnp.zeros((1 + mp, rule.block, G * D), jnp.float32)
    ck = jnp.asarray(rng.normal(size=(1, -(-T // rule.stride), G * D)),
                     jnp.float32)                     # a reused slot's rows
    got = []
    for a in range(0, T, chunk):
        n = min(chunk, T - a)
        part = np.zeros((1, chunk, G, D), np.float32)
        part[0, :n] = k[a:a + n]
        ctx, ln = jnp.asarray([a]), jnp.asarray([n])
        kp, _ = PA.paged_prefill_write(kp, kp, jnp.asarray(part),
                                       jnp.asarray(part), tbl, ctx, ln)
        ck = SS.compressed_update(ck, kp, tbl, ctx, ln, rule,
                                  -(-chunk // rule.stride))
        qq = np.zeros((1, chunk, G * R_, D), np.float32)
        qq[0, :n] = q[a:a + n]
        got.append(np.asarray(SS.select_blocks(jnp.asarray(qq), ck, ctx,
                                               rule, mp))[0, :n])
    got = np.concatenate(got)                         # [T, G, topk]
    idx, ok, n_c = R.overlaps(m, T)
    c = jax.vmap(lambda i: jnp.mean(jax.lax.dynamic_slice_in_dim(
        jnp.asarray(k), i * rule.stride, rule.kernel), 0))(jnp.arange(n_c))
    want = np.asarray(R.chosen_blocks(
        m, jnp.asarray(q.reshape(T, G, R_, D)), jnp.arange(T), c, idx, ok))
    for t in range(rule.dense_len - 1, T):            # the sparse queries
        for g in range(G):
            assert set(got[t, g]) == set(np.flatnonzero(want[t, g])), (t, g)


# ---- the engine ------------------------------------------------------------------

def test_the_engine_serves_the_reference(tiny):
    """Three requests on two slots (one slot is reused), prompts that cross
    the dense length, chunk 12 on pages of 8: every served token is the
    reference's own choice, and the counters add up."""
    model, m, src = tiny
    eng = ContinuousBatchingEngine(model, num_slots=2, max_len=96,
                                   page_size=8, prefill_chunk=12,
                                   decode_chunk=4, greedy=True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, n).astype(np.int32) for n in (70, 53, 61)]
    rids = [eng.add_request(p, 9) for p in prompts]
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.request_id] = r
    for rid, p in zip(rids, prompts):
        toks = np.asarray(done[rid].tokens, np.int32)
        assert len(toks) == 9
        ids = np.concatenate([p, toks])[None]
        lg = _ref_logits(m, src, ids)[0, len(p) - 1:len(p) - 1 + 9]
        gap = lg.max(-1) - lg[np.arange(9), toks]
        assert gap.max() <= TOL
    g = eng.gauges()
    # sparse query-groups: prompt positions past 48 and the 8 decode passes
    # after the first token, per request, x 2 layers x 2 groups
    sparse = sum((len(p) - 48) + 8 for p in prompts) * 4
    assert g["sparse_queries"] == sparse
    assert g["sparse_blocks_selected"] == sparse * 5
    # off the TPU the oracle gathers the slot's whole table a query
    assert g["sparse_blocks_loaded"] == sparse * eng.pages_per_slot
    assert g["sparse_queries"] + g["dense_queries"] \
        == sum(len(p) + 8 for p in prompts) * 4


def test_the_cache_spec_and_what_the_engine_refuses(tiny):
    model, _, _ = tiny
    spec = model.cache_spec()
    assert spec == [SlotState((4, 16, 16), "float32"), PagedKV(2, 16),
                    CompressedKeys(2, 16, 4), SlotState((4, 16, 16),
                                                        "float32"),
                    PagedKV(2, 16), CompressedKeys(2, 16, 4),
                    StepCounters(COUNTERS)]
    eng = ContinuousBatchingEngine(model, num_slots=3, max_len=50,
                                   page_size=8, prefill_chunk=12)
    # the index is sized by the ENGINE's max_len: ceil(50 / 4) rows
    assert [tuple(p._data.shape) for p in eng.pools][3] == (3, 13, 32)
    assert eng._prefix_cache is False
    with pytest.raises(ValueError, match="speculative"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=50,
                                 page_size=8, spec_decode=True)
    with pytest.raises(ValueError, match="migration"):
        ContinuousBatchingEngine(model, num_slots=2, max_len=50,
                                 page_size=8, role="prefill")


# ---- the lightning rule's forms --------------------------------------------------

def test_the_block_form_is_the_one_step_rule():
    """SSD with one group a head (x = v, B = k, C = q, dt 1 on valid
    positions and 0 on padding, A = -s) from a non-zero state: the same
    outputs and final state (transposed) as the one-step rule applied
    position by position, stopping at each row's length."""
    rng = np.random.default_rng(9)
    Bn, S, H, d = 2, 19, 3, 8
    q, k, v = (rng.normal(size=(Bn, S, H, d)).astype(np.float32)
               for _ in range(3))
    s = np.asarray([0.05, 0.3, 0.9], np.float32)
    S0 = rng.normal(size=(Bn, H, d, d)).astype(np.float32)
    lens = np.asarray([19, 7])
    dt = (np.arange(S)[None] < lens[:, None]).astype(np.float32)
    o, h = ssd_chunked(jnp.swapaxes(S0, -1, -2), v,
                       np.broadcast_to(dt[..., None], (Bn, S, H)), -s, k, q,
                       np.zeros(H, np.float32), chunk=4)
    st = jnp.asarray(S0)
    dec = np.broadcast_to(np.exp(-s), (Bn, H)).astype(np.float32)
    for t in range(S):
        live = jnp.asarray(t < lens)
        ot, st = gd.linear_state_step(st, q[:, t], k[:, t], v[:, t], dec,
                                      live=live)
        for b in range(Bn):
            if t < lens[b]:
                np.testing.assert_allclose(o[b, t], ot[b], atol=1e-4)
    np.testing.assert_allclose(jnp.swapaxes(h, -1, -2), st, atol=1e-4)
