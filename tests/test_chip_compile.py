"""The Pallas kernels of the main paths, compiled for a DESCRIBED v5e.

Interpret mode (every other kernel test) never checks what the TPU
compiler checks: block shapes against the (8, 128) tiling, in-kernel
relayouts, scoped VMEM, and that a Mosaic call under a mesh sits inside
a ``shard_map``. The TPU compiler is installed here and compiles for a
chip that is described, not attached — no chip time, about two seconds
a case. Nothing runs, so this says nothing about results or speed;
``chip_smoke.py`` is the run.

Rules this file keeps (the ``on-chip-measurement`` guide, section 2):
the topology is described inside a module-scoped, non-autouse fixture
(never at import time, never in ``skipif``/``parametrize``), compiles
happen in the test's own process, the persistent compilation cache is
off around them, and all such tests live in this ONE file (only one
process may load the TPU library).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip (the next one warns
    and recompiles): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def native(monkeypatch, no_persistent_cache):
    """Compile the kernels natively: off-TPU each kernel module asks
    ``_interpret()`` and would lower the interpreter instead. Steered
    here, in the test — the program has no option for it."""
    from paddle_tpu.ops.pallas import (ce_chunk, flash_attention,
                                       gated_delta_step, grouped_matmul,
                                       ragged_paged_attention, rms_norm,
                                       swiglu)
    for mod in (ce_chunk, flash_attention, gated_delta_step, grouped_matmul,
                ragged_paged_attention, rms_norm, swiglu):
        monkeypatch.setattr(mod, "_interpret", lambda: False)


def _compile(fn, *args):
    """Lower + compile ``fn`` for the described chip; the compiled text
    must hold the Mosaic custom call (i.e. the kernel, not a jnp
    path)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sds(sharding):
    return lambda shape, dtype=BF16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


# ---- serving: ragged paged attention -------------------------------------

def _compile_ragged(s, quant, b, c, kvh, rep, page, pps, n_pages, d=128,
                    window=None):
    from paddle_tpu.ops.paged_attention import (kv_pool_shape,
                                                kv_scales_shape)
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    pool = s(kv_pool_shape(kvh, n_pages, page, d),
             jnp.int8 if quant else BF16)
    args = [s((b, c, kvh * rep, d)), pool, pool,
            s((b, pps), jnp.int32), s((b,), jnp.int32),
            s((b,), jnp.int32)]
    if quant:
        scales = s(kv_scales_shape(kvh, n_pages, page), jnp.float32)
        args += [scales, scales]

        def fn(q, k, v, t, ctx, ln, ks, vs):
            return ragged_paged_attention(q, k, v, t, ctx, ln,
                                          k_scales=ks, v_scales=vs,
                                          window=window)
    else:
        def fn(q, k, v, t, ctx, ln):
            return ragged_paged_attention(q, k, v, t, ctx, ln,
                                          window=window)
    _compile(fn, *args)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("rep", [1, 4, 7])
@pytest.mark.parametrize("c,page", [(1, 16), (16, 16), (64, 32)],
                         ids=["decode", "chunk16", "chunk64_page32"])
def test_ragged_paged_attention(native, one_chip, quant, rep, c, page):
    """GQA ratios 1 (MHA), 4 (Llama-3-8B) and 7 (Qwen2-7B), d128, pure
    decode and a prefill chunk, plain and quantized pools."""
    _compile_ragged(_sds(one_chip), quant, b=8, c=c, kvh=4, rep=rep,
                    page=page, pps=64, n_pages=512)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("kvh,rep", [(4, 7), (2, 16)],
                         ids=["qwen2_7b", "nemotron_h"])
def test_ragged_paged_attention_at_the_group_shape(native, one_chip, quant,
                                                   kvh, rep):
    """One group of the serving step's prefill loop: 8 rows x a chunk of
    128, page 16, over the benchmark's pools (64 slots x 2048), at the
    two GQA ratios the serving cells run."""
    _compile_ragged(_sds(one_chip), quant, b=8, c=128, kvh=kvh, rep=rep,
                    page=16, pps=128, n_pages=8193)


# the serving cells' layers: (kv heads, GQA ratio, pages a slot, pages a
# pool, window[, head_dim 128]). K-EXAONE's window layers read a 320-wide
# table that cycles through a slot's ring of 17 pages (1,089 = 64 x 17 + 1);
# Qwen3-Next's gated attention has heads of 256: 512 columns a token
_CELL_LAYERS = {"qwen2_7b": (4, 7, 128, 8193, None),
                "nemotron_h": (2, 16, 128, 8193, None),
                "k_exaone_global": (8, 8, 320, 20481, None),
                "k_exaone_ring": (8, 8, 320, 1089, 128),
                "qwen3_next_d256": (2, 8, 320, 20481, None, 256)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("c,b", [(1, 64), (128, 8)], ids=["decode", "group"])
@pytest.mark.parametrize("layer", sorted(_CELL_LAYERS))
def test_ragged_paged_attention_at_the_cells_shapes(native, one_chip, layer,
                                                    c, b, quant):
    """A decode micro-step (64 slots x one token: every kv head of a
    sequence in one program, whole-page copies) and a prefill group (8
    rows x 128) of every attention layer the serving cells run, at the
    blocks their shapes resolve to."""
    kvh, rep, pps, n_pages, window, *d = _CELL_LAYERS[layer]
    _compile_ragged(_sds(one_chip), quant, b=b, c=c, kvh=kvh, rep=rep,
                    page=16, pps=pps, n_pages=n_pages, window=window,
                    d=d[0] if d else 128)


@pytest.mark.parametrize("c", [1, 16], ids=["decode", "chunk16"])
def test_ragged_paged_attention_at_head_dim_64(native, one_chip, c):
    """GPT-2's 12 heads of 64: until PR 35 Mosaic refused the kernel's
    copy of one kv head's 64 columns ("must be aligned to tiling (128)");
    a copy now brings 6 heads' 384 columns and a head is a slice of the
    VMEM buffer. Compiled, not yet run on the chip (PERF.md section 7)."""
    _compile_ragged(_sds(one_chip), False, b=8, c=c, kvh=12, rep=1,
                    page=16, pps=64, n_pages=513, d=64)


def test_a_stack_lowers_the_kernel_once_a_kind_of_layer(native, one_chip):
    """What a call site costs the host, counted and not timed: a stack
    walked in Python as K-EXAONE's is — four window layers over rings,
    one global layer over the long table, KVH 8 — lowers to a module that
    holds TWO kernel bodies, not five. The kernel's call is a module-level
    jitted function with its block choices as static arguments, so the
    calls of one signature share one jaxpr and one lowered function
    (PERF.md section 6, PR 35; PR 34's five bodies cost the K-EXAONE
    cell 4.3 s of set-up)."""
    from paddle_tpu.ops.paged_attention import kv_pool_shape
    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention)
    s = _sds(one_chip)
    b, kvh, rep, d, page, pps = 8, 8, 1, 128, 16, 16
    q = s((b, 1, kvh * rep, d))
    ring = [s(kv_pool_shape(kvh, b * 9 + 1, page, d))] * 2
    pool = [s(kv_pool_shape(kvh, b * pps + 1, page, d))] * 2
    tbl, vec = s((b, pps), jnp.int32), s((b,), jnp.int32)

    def stack(q, kr, vr, kg, vg, t_ring, t, ctx, ln):
        for _ in range(4):
            q = ragged_paged_attention(q, kr, vr, t_ring, ctx, ln,
                                       window=128)
        return ragged_paged_attention(q, kg, vg, t, ctx, ln)

    lowered = jax.jit(stack).lower(q, *ring, *pool, tbl, tbl, vec, vec)
    assert lowered.as_text().count("@tpu_custom_call") == 2
    assert lowered.compile().as_text().count("tpu_custom_call") >= 5


def _compile_serving_step(one_chip, topo, monkeypatch, kvh, kv_quant):
    """(lowered, compiled text, engine) of the unified step program of a
    2-layer model at Qwen2-7B's widths, 64 slots x 2048, page 16."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import Qwen2Config, Qwen2ForCausalLM
    from paddle_tpu.nn import initializer

    def no_storage(self, shape, dtype):
        # 1.6 B parameters are only shapes here
        a = jnp.zeros(tuple(shape), BF16)
        a.delete()
        return a

    cfg = Qwen2Config.qwen2_7b()
    cfg.vocab_size, cfg.num_hidden_layers = 152064, 2
    cfg.num_key_value_heads = kvh
    cfg.max_position_embeddings = 32768
    cfg.scan_layers = cfg.tensor_parallel = False
    with monkeypatch.context() as m:
        m.setattr(initializer.Normal, "__call__", no_storage)
        model = Qwen2ForCausalLM(cfg)
    model.eval()
    for p in model.parameters():
        if p._data.dtype != BF16:              # the norm scales
            p._data = p._data.astype(BF16)
    eng = ContinuousBatchingEngine(model, num_slots=64, max_len=2048,
                                   page_size=16, greedy=True,
                                   kv_quant=kv_quant)
    return _lower_step(model, eng, one_chip, topo, monkeypatch) + (eng,)


def _lower_step(model, eng, one_chip, topo, monkeypatch):
    """(lowered, compiled text) of ``eng``'s unified step program for the
    described chip, the model's parameters as shapes."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    params = list(model.parameters())
    assert (eng._group, eng.prefill_chunk, eng.decode_chunk) == (8, 128, 16)
    ustep = eng._unified_static().function
    s = _sds(one_chip)
    B, i32 = eng.num_slots, jnp.int32
    # the turn's one upload, the chained tok / ctx / active, the key
    args = [s((eng._unified_up.size,), i32), s((B,), i32), s((B,), i32),
            s((B,), bool), s((2,), jnp.uint32)]
    args += [s(tuple(p._data.shape), p._data.dtype) for p in eng.pools]
    # the call sites ask which platform they run on: answer for the
    # described chip while the program is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))

    def step(leaves, *arrays):
        held = [p._data for p in params]
        for p, a in zip(params, leaves):
            p._data = a
        try:
            with paddle.no_grad():
                outs = ustep(*[Tensor(a) for a in arrays])
        finally:
            for p, a in zip(params, held):
                p._data = a
        return [o._data for o in outs]

    lowered = jax.jit(step).lower(
        [s(tuple(p.shape), BF16) for p in params], *args)
    return lowered, lowered.compile().as_text()


def _pool_copies(text, eng):
    """Pool-shaped ``copy`` ops of a compiled step, per pool kind
    (``"kv"`` data pools, ``"scale"`` pools, ``"wkv"`` window rings,
    ``"state"`` per-slot state arrays):
    ``{kind: (in the whole
    program, inside a ``while`` body or anything a body calls)}``. A
    pool that the write and the kernel hold in two layouts shows here as
    one copy per pool per pass."""
    import re
    names = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}
    kind_of = {"%s[%s]" % (names[str(jnp.dtype(dt))],
                           ",".join(map(str, sh))): kind
               for sh, dt, kind in zip(eng._pool_shapes, eng._pool_dtypes,
                                       eng._pool_kinds)
               if kind in ("kv", "scale", "wkv", "state")}
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    called = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
    inside, todo = set(), [
        b for ls in comps.values() for ln in ls
        for b in re.findall(r" while\(.*body=%?([\w.\-]+)", ln)]
    while todo:
        n = todo.pop()
        if n not in inside:
            inside.add(n)
            todo += called.findall("\n".join(comps.get(n, ())))
    a_copy = re.compile(r"= (\S+?\])(?:\{[^}]*\})? copy\(")
    out = {kind: [0, 0] for kind in kind_of.values()}
    for n, ls in comps.items():
        for ln in ls:
            m = a_copy.search(ln)
            if m and m.group(1) in kind_of:
                out[kind_of[m.group(1)]][0] += 1
                out[kind_of[m.group(1)]][1] += n in inside
    return {k: tuple(v) for k, v in out.items()}


@pytest.mark.parametrize("kvh,kv_quant", [(4, "none"), (4, "int8"),
                                          (2, "none")],
                         ids=["bf16", "int8kv", "nemotron_gqa"])
def test_serving_step_program_at_qwen2_widths(native, one_chip, topo,
                                              monkeypatch, kvh, kv_quant):
    """The unified step program — the loop over groups of 8 prefilling
    slots, the 16 decode micro-steps, the head on one row per slot — of a
    2-layer model at Qwen2-7B's widths, 64 slots x 2048, compiled whole:
    two loops (the group loop's trip count is data), and the attention
    kernel in both bodies. The K/V write and the kernel share ONE pool
    layout: no pool-sized ``copy`` inside either loop, at most one per
    K/V pool in the whole program (the entry copy of an argument the step
    does not donate) — bf16 and int8 pools, and Nemotron-3's GQA ratio
    (2 kv heads). The f32 scales pools of int8 K/V (2 MB each) read 3
    copies a pool BETWEEN the loops: XLA gives the two loops' scatters
    two layouts of so narrow an array; none inside a loop
    (``PERF.md`` section 7)."""
    lowered, text, eng = _compile_serving_step(one_chip, topo, monkeypatch,
                                               kvh, kv_quant)
    assert lowered.as_text().count("stablehlo.while") == 2
    assert "ragged_paged_attention" in text
    rep = 28 // kvh
    for shape in (f"bf16[8,{kvh},{128 * rep},128]",
                  f"bf16[64,{kvh},{-(-rep // 8) * 8},128]"):
        assert shape in text           # the kernel at 8 rows, and at 64
    for shape in ("bf16[8192,", "bf16[64,128,152064]"):
        assert shape not in text       # no pass at all 64 x 128 positions
    copies = _pool_copies(text, eng)
    assert all(in_loops == 0 for _, in_loops in copies.values()), copies
    assert copies["kv"][0] <= eng._pool_kinds.count("kv"), copies


def test_serving_step_takes_one_host_array(native, one_chip, topo,
                                           monkeypatch):
    """What the host sends a turn is ONE array: the step program compiled
    for the described chip has, besides the weights, exactly one entry
    parameter of host origin — the flat int32 upload that holds the prompt
    chunks, the row list, the block tables, limits, stop tokens and context
    resets — next to the chained tok / ctx / active state, the key and the
    pools. An admission that shipped its own arrays, or a table kept on
    the device, would show here as a further parameter."""
    import re
    _, text, eng = _compile_serving_step(one_chip, topo, monkeypatch, 4,
                                         "none")
    B, C, MP = eng.num_slots, eng.prefill_chunk, eng.pages_per_slot
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    shapes = re.findall(r"= (\S+?)(?:\{[^}]*\})? parameter\(\d+\)", entry)
    n_weights = len(list(eng.model.parameters()))
    chained = [f"s32[{B}]", f"s32[{B}]", f"pred[{B}]", "u32[2]"] + [
        "bf16[%s]" % ",".join(map(str, p._data.shape)) for p in eng.pools]
    upload = B * (C + MP + 6) + eng._group_rows + 1
    assert eng._unified_up.size == upload
    assert shapes[n_weights:] == [f"s32[{upload}]"] + chained


def test_serving_step_program_at_k_exaone_widths(native, one_chip, topo,
                                                 monkeypatch):
    """The ONE step program of a 2-layer model at K-EXAONE's widths
    (hidden 6144, 64 / 8 heads x 128, 16 held experts of 128 x 2048): a
    window layer over per-slot rings beside a global one over host-managed
    pages, both sparse, 64 slots x 5120. It compiles for the described
    chip with the kernel in both loops, the window pool is sized by the
    window — 17 pages a slot — and no pool, ring or global, is re-laid out
    inside a loop."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import ExaoneMoeConfig, ExaoneMoeForCausalLM
    cfg = ExaoneMoeConfig.k_exaone_236b()
    cfg.num_hidden_layers, cfg.vocab_size = 2, 19200
    cfg.layer_types = ("sliding_attention", "full_attention")
    cfg.mlp_layer_types = ("sparse", "sparse")
    cfg.num_experts_held = 16
    cfg.dtype, cfg.empty_init = "bfloat16", True
    model = ExaoneMoeForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(model, num_slots=64, max_len=5120,
                                   page_size=16, greedy=True)
    assert eng._ring == 17
    assert [tuple(p._data.shape) for p in eng.pools] \
        == [(64 * 17 + 1, 16, 1024)] * 2 + [(64 * 320 + 1, 16, 1024)] * 2 \
        + [(3,)]
    lowered, text = _lower_step(model, eng, one_chip, topo, monkeypatch)
    # the group loop and the micro-step scan, and inside each the expert
    # sort's searchsorted (a while of its own)
    assert lowered.as_text().count("stablehlo.while") == 4
    assert "ragged_paged_attention" in text and "grouped_matmul" in text
    for shape in ("bf16[8,8,1024,128]", "bf16[64,8,8,128]"):
        assert shape in text           # the kernel at 8 rows, and at 64
    copies = _pool_copies(text, eng)
    assert set(copies) == {"kv", "wkv"}
    assert all(in_loops == 0 for _, in_loops in copies.values()), copies


def test_gated_delta_step_at_the_cells_shape(native, one_chip):
    """The one-step delta-rule kernel at a decode micro-step of the
    Qwen3-Next cell: 64 slots x 32 heads of 128 x 128 float32 state, 16
    heads a program, key columns sliced along the lanes, the state written
    over its input: the compiled program holds no second copy of the 134 MB
    state (temporaries stay under 1 MB)."""
    from paddle_tpu.ops.pallas.gated_delta_step import gated_delta_step
    s = _sds(one_chip)
    f32 = jnp.float32
    vec = s((64, 32, 128), f32)
    compiled = jax.jit(gated_delta_step, donate_argnums=(0,)).lower(
        s((64, 32, 128, 128), f32), vec, vec, vec, s((64, 32), f32),
        s((64, 32), f32), s((64,), bool)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 64 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 20


def test_serving_step_program_at_qwen3_next_widths(native, one_chip, topo,
                                                   monkeypatch):
    """The ONE step program of a 4-layer model at Qwen3-Next's widths
    (hidden 2048; three delta-rule layers of 32 x 128 x 128 state beside one
    gated attention layer of 16 / 2 heads x 256; 64 held experts of 512 x
    512): per-slot float32 state beside host-managed pages, 64 slots x
    5120. It compiles for the described chip with the attention kernel in
    both loops, the one-step delta-rule kernel in the decode scan and the
    chunked form (its triangular solve) in the group loop, and neither loop
    holds a copy of a whole state array."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    cfg = Qwen3NextConfig.qwen3_next_80b_a3b()
    cfg.num_hidden_layers, cfg.vocab_size = 4, 18992
    cfg.num_experts_held = 64
    cfg.dtype, cfg.empty_init = "bfloat16", True
    model = Qwen3NextForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(model, num_slots=64, max_len=5120,
                                   page_size=16, greedy=True)
    assert [tuple(p._data.shape) for p in eng.pools] \
        == [(64, 32, 128, 128), (64, 3, 8192)] * 3 \
        + [(64 * 320 + 1, 16, 512)] * 2 + [(5,)]
    assert eng.gauges()["state_pool_bytes"] == 3 * 64 * (2_097_152 + 49_152)
    lowered, text = _lower_step(model, eng, one_chip, topo, monkeypatch)
    for kernel in ("ragged_paged_attention", "grouped_matmul",
                   "gated_delta_step"):
        assert kernel in text
    for shape in ("bf16[8,2,1024,256]", "bf16[64,2,8,256]"):
        assert shape in text           # the attention kernel at 8 rows, at 64
    # ONE lowered body of the step kernel for the three delta-rule layers,
    # called from the decode scan alone
    assert lowered.as_text().count("gated_delta_step") == 1
    copies = _pool_copies(text, eng)
    assert set(copies) == {"kv", "state"} and copies["kv"][1] == 0, copies
    # the 134 MB states: only the entry copy of an argument the step does
    # not donate. (The 3 MB conv tails are re-laid out once a micro-step:
    # XLA keeps ``[64, 3, 8192]`` slot-major in one loop and tap-major in
    # the other.)
    import re
    loops = text.split("\nENTRY ")[0]
    assert not re.findall(r"= f32\[64,32,128,128\]\S* copy\(", loops)


def test_ragged_surface_offers_only_accepted_blocks(native, one_chip):
    """Every block the tuner surface offers compiles AS GIVEN (a q
    block the wrapper would have to round is not a candidate)."""
    from paddle_tpu.ops.pallas import ragged_paged_attention as rpa
    from paddle_tpu.tuner.surface import get_surface
    surf = get_surface("ragged_paged_attention")
    shape = {"c": 48, "pages": 4, "page": 16, "d": 128}
    cands = [c for c in surf.candidates(shape) if surf.is_valid(c, shape)]
    assert cands and not surf.is_valid(
        {"q_block": 4, "kv_pages_per_block": 1}, shape)
    for cand in cands:
        assert rpa._row_blocking(48, cand["q_block"], 7)[0] \
            == cand["q_block"]
    from paddle_tpu.ops.paged_attention import kv_pool_shape
    s = _sds(one_chip)
    pool = s(kv_pool_shape(4, 64, 16, 128))
    for cand in (cands[0], cands[-1]):
        _compile(lambda q, k, v, t, ctx, ln, _c=cand:
                 rpa.ragged_paged_attention(
                     q, k, v, t, ctx, ln, q_block=_c["q_block"],
                     kv_pages_per_block=_c["kv_pages_per_block"]),
                 s((2, 48, 28, 128)), pool, pool,
                 s((2, 4), jnp.int32), s((2,), jnp.int32),
                 s((2,), jnp.int32))


# ---- training kernels ----------------------------------------------------

def _grad_sum(fn):
    """fwd+bwd of ``fn`` w.r.t. every argument, reduced to a scalar."""
    def loss(*args):
        out = fn(*args)
        outs = out if isinstance(out, (tuple, list)) else (out,)
        return sum(jnp.sum(o.astype(jnp.float32)) for o in outs)
    return lambda *args: jax.grad(loss, argnums=tuple(
        range(len(args))))(*args)


@pytest.mark.parametrize("d,heads", [(128, 28), (64, 12)],
                         ids=["d128", "d64"])
def test_flash_attention_fwd_bwd(native, one_chip, d, heads):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    s = _sds(one_chip)
    q = s((2, 1024, heads, d))
    text = _compile(_grad_sum(
        lambda q, k, v: flash_attention(q, k, v, True, None)), q, q, q)
    assert text.count("tpu_custom_call") >= 3       # fwd, dkv, dq


@pytest.mark.parametrize("h", [3584, 4096])
def test_rms_norm_residual_fwd_bwd(native, one_chip, h):
    """Default row block at the widths where 256 rows overflow scoped
    VMEM (16.0 MB fwd / 16.8 MB bwd at h4096): the block is derived
    from the width."""
    from paddle_tpu.ops.pallas.rms_norm import (rms_norm,
                                                rms_norm_residual)
    s = _sds(one_chip)
    x = s((4096, h))
    _compile(_grad_sum(lambda x, r, w: rms_norm_residual(x, r, w, 1e-6)),
             x, x, s((h,)))
    _compile(_grad_sum(lambda x, w: rms_norm(x, w, 1e-6)), x, s((h,)))


def test_rms_surfaces_offer_only_blocks_that_fit():
    from paddle_tpu.ops.pallas.rms_norm import _max_rows
    from paddle_tpu.tuner.surface import get_surface
    assert (_max_rows(2048), _max_rows(3584), _max_rows(4096)) \
        == (256, 128, 128)
    for name in ("rms_norm", "rms_norm_residual"):
        surf = get_surface(name)
        ok = [c["block_rows"] for c in surf.candidates({"d": 4096})
              if surf.is_valid(c, {"d": 4096})]
        assert ok == [64, 128]


def test_swiglu_fwd_bwd(native, one_chip):
    from paddle_tpu.ops.pallas.swiglu import swiglu_fused
    g = _sds(one_chip)((2048, 18944))
    _compile(_grad_sum(swiglu_fused), g, g)


@pytest.mark.parametrize("chunk", [1024, 8192])
def test_ce_chunk_pair(native, one_chip, chunk):
    """The stats/dlogits pair at the default chunk and at the widest
    one the ``fused_ce`` surface can select."""
    from paddle_tpu.ops.pallas.ce_chunk import chunk_dlogits, chunk_stats
    s = _sds(one_chip)
    n = 8192
    logits = s((n, chunk), jnp.float32)
    vec_i, vec_f = s((n,), jnp.int32), s((n,), jnp.float32)
    lo = s((), jnp.int32)
    _compile(chunk_stats, logits, vec_i, lo)
    _compile(lambda lg, lse, loc, sc, lo_: chunk_dlogits(
        lg, lse, loc, sc, lo_, out_dtype=BF16),
        logits, vec_f, vec_i, vec_f, lo)


def test_grouped_matmul_fwd_bwd(native, one_chip):
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul
    s = _sds(one_chip)
    e, d, h, bm, nr = 8, 2048, 1408, 128, 24
    _compile(
        lambda x, w, gid: jax.grad(
            lambda x_, w_: jnp.sum(
                grouped_matmul(x_, w_, gid).astype(jnp.float32)),
            argnums=(0, 1))(x, w),
        s((nr * bm, d)), s((e, d, h)), s((nr,), jnp.int32))


# ---- under a mesh --------------------------------------------------------

@pytest.mark.parametrize("tokens", [64, 64 * 128])
def test_held_expert_layer_at_published_widths(native, one_chip, tokens):
    """The held-share expert layer (ops.moe.moe_experts_held) as the
    Nemotron-3-Super cell runs it: 128 held experts of 512, latent 1024,
    expert width 2688, 22 pairs a token — a decode step's 64 tokens (row
    tile 16) and a mixed pass's 64 x 128 positions (row tile 128, live
    tiles only)."""
    from paddle_tpu.ops import moe

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(v, idx, w, w1, w2, valid):
        return moe.moe_experts_held(v, idx, w, w1, w2, 0, valid=valid)

    _compile(fn, sds((tokens, 1024), BF16), sds((tokens, 22), jnp.int32),
             sds((tokens, 22), jnp.float32), sds((128, 1024, 2688), BF16),
             sds((128, 2688, 1024), BF16), sds((tokens,), jnp.bool_))


def test_kernels_under_a_2x2_mesh(native, topo):
    """Mosaic kernels cannot be partitioned automatically. Under a
    fleet mesh (sharding=2 x model=2, the ``--chips 4`` phase) the call
    sites wrap them in ``shard_map`` over the axes their operands are
    sharded on: flash attention with batch over ``sharding`` and heads
    over ``model``, rms_norm with rows over ``sharding``."""
    from paddle_tpu.ops.pallas._mesh import sharded_heads, sharded_rows
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.rms_norm import rms_norm
    mesh = Mesh(np.array(topo.devices).reshape(2, 2),
                ("sharding", "model"))

    def s(shape, spec, dtype=BF16):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    qkv = s((4, 1024, 16, 128), P("sharding", None, "model", None))
    text = _compile(
        _grad_sum(lambda q, k, v: sharded_heads(
            lambda a, b, c: flash_attention(a, b, c, True, None),
            mesh, q, k, v)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3
    x = s((4, 1024, 2048), P("sharding", None, None))
    _compile(_grad_sum(lambda x_, w: sharded_rows(
        lambda a, b: rms_norm(a, b, 1e-6), mesh, x_, replicated=(w,))),
        x, s((2048,), P()))
