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
    from paddle_tpu.ops.pallas import (flash_attention, gated_delta_step,
                                       grouped_matmul, linear_ce,
                                       ragged_paged_attention, rms_norm,
                                       sparse_paged_attention, swiglu)
    for mod in (flash_attention, gated_delta_step, grouped_matmul,
                linear_ce, ragged_paged_attention, rms_norm,
                sparse_paged_attention, swiglu):
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
    """(lowered, compiled text, memory analysis, engine) of the unified
    step program of a 2-layer model at Qwen2-7B's widths, 64 slots x
    2048, page 16."""
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


def _lower_step(model, eng, one_chip, topo, monkeypatch,
                chunks=(8, 128, 16)):
    """(lowered, compiled text, memory analysis) of ``eng``'s unified
    step program for the described chip, the model's parameters as
    shapes, bound as ``to_static`` binds it: the pools donated.
    ``chunks``: the engine's (group rows, prefill chunk, decode chunk)."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    params = list(model.parameters())
    assert (eng._group, eng.prefill_chunk, eng.decode_chunk) == chunks
    ustep = eng._unified_static().function
    s = _sds(one_chip)
    B, i32 = eng.num_slots, jnp.int32
    # the turn's one upload, the chained tok / ctx / active, the key
    args = [s((eng._unified_up.size,), i32), s((B,), i32), s((B,), i32),
            s((B,), bool), s((2,), jnp.uint32)]
    # the call sites ask which platform they run on: answer for the
    # described chip while the program is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    state = eng.pools + params

    def step(pools, leaves, *arrays):
        # as to_static binds it: the pools are the state the step
        # reassigns (argument 0, donated), the weights the state it reads
        held = [t._data for t in state]
        for t, a in zip(state, list(pools) + list(leaves)):
            t._data = a
        try:
            with paddle.no_grad():
                outs = ustep(*[Tensor(a) for a in arrays])
            new_pools = [p._data for p in eng.pools]
        finally:
            for t, a in zip(state, held):
                t._data = a
        return new_pools, [o._data for o in outs]

    lowered = jax.jit(step, donate_argnums=(0,)).lower(
        [s(tuple(p._data.shape), p._data.dtype) for p in eng.pools],
        [s(tuple(p.shape), BF16) for p in params], *args)
    compiled = lowered.compile()
    return lowered, compiled.as_text(), compiled.memory_analysis()


def _assert_pools_donated(text, mem, eng):
    """The step writes every pool it reads into the buffer it was given:
    its first parameters are the pools, each aliased to that pool's
    output, and the aliased bytes are the pools' bytes. The step zeroes
    its pass counters (kind ``"counters"``) instead of reading them, so
    that pool is no parameter. The f32 scales pools of int8 K/V are laid
    out with the page axis minor, in whole tiles of 128 pages."""
    import re
    read = [i for i, k in enumerate(eng._pool_kinds) if k != "counters"]
    aliases = text[text.index("input_output_alias={"):]
    aliases = aliases[:aliases.index(" }")]
    assert re.findall(r"\{(\d+)\}: \((\d+), \{\}", aliases) \
        == [(str(i), str(j)) for j, i in enumerate(read)], aliases

    def nbytes(shape, dtype, kind):
        if kind == "scale":
            shape = (-(-shape[0] // 128) * 128,) + tuple(shape[1:])
        return int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes == sum(
        nbytes(eng._pool_shapes[i], eng._pool_dtypes[i], eng._pool_kinds[i])
        for i in read)


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
    kernel in both bodies. The pools are donated and the K/V write and
    the kernel share ONE pool layout: no copy of a K/V pool anywhere in
    the program (until the pools were donated, each was copied once at
    entry) — bf16 and int8 pools, and Nemotron-3's GQA ratio (2 kv
    heads). The f32 scales pools of int8 K/V (2 MB each) read 3 copies a
    pool BETWEEN the loops: XLA gives the two loops' scatters two layouts
    of so narrow an array; none inside a loop, and no more with the
    pools donated (``PERF.md`` section 7)."""
    lowered, text, mem, eng = _compile_serving_step(
        one_chip, topo, monkeypatch, kvh, kv_quant)
    assert lowered.as_text().count("stablehlo.while") == 2
    assert "ragged_paged_attention" in text
    rep = 28 // kvh
    for shape in (f"bf16[8,{kvh},{128 * rep},128]",
                  f"bf16[64,{kvh},{-(-rep // 8) * 8},128]"):
        assert shape in text           # the kernel at 8 rows, and at 64
    for shape in ("bf16[8192,", "bf16[64,128,152064]"):
        assert shape not in text       # no pass at all 64 x 128 positions
    copies = _pool_copies(text, eng)
    assert copies["kv"] == (0, 0), copies
    if kv_quant == "int8":
        assert copies["scale"] == (3 * 4, 0), copies
    _assert_pools_donated(text, mem, eng)


def test_serving_step_takes_one_host_array(native, one_chip, topo,
                                           monkeypatch):
    """What the host sends a turn is ONE array: the step program compiled
    for the described chip has, besides the pools it is given (donated,
    first) and the weights, exactly one entry parameter of host origin —
    the flat int32 upload that holds the prompt chunks, the row list, the
    block tables, limits, stop tokens and context resets — next to the
    chained tok / ctx / active state and the key. An admission that
    shipped its own arrays, or a table kept on the device, would show
    here as a further parameter."""
    import re
    _, text, _, eng = _compile_serving_step(one_chip, topo, monkeypatch, 4,
                                            "none")
    B, C, MP = eng.num_slots, eng.prefill_chunk, eng.pages_per_slot
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    shapes = [sh for _, sh in sorted(
        (int(n), sh) for sh, n in re.findall(
            r"= (\S+?)(?:\{[^}]*\})? parameter\((\d+)\)", entry))]
    n_weights = len(list(eng.model.parameters()))
    pools = ["bf16[%s]" % ",".join(map(str, p._data.shape))
             for p in eng.pools]
    chained = [f"s32[{B}]", f"s32[{B}]", f"pred[{B}]", "u32[2]"]
    upload = B * (C + MP + 6) + eng._group_rows + 1
    assert eng._unified_up.size == upload
    assert shapes[:len(pools)] == pools
    assert shapes[len(pools) + n_weights:] == [f"s32[{upload}]"] + chained


def test_serving_step_program_at_k_exaone_widths(native, one_chip, topo,
                                                 monkeypatch):
    """The ONE step program of a 2-layer model at K-EXAONE's widths
    (hidden 6144, 64 / 8 heads x 128, 16 held experts of 128 x 2048): a
    window layer over per-slot rings beside a global one over host-managed
    pages, both sparse, 64 slots x 5120. It compiles for the described
    chip with the kernel in both loops, the window pool is sized by the
    window — 17 pages a slot — and no pool, ring or global, is copied
    anywhere: the step is given them (donated) and writes them in
    place."""
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
    lowered, text, mem = _lower_step(model, eng, one_chip, topo,
                                     monkeypatch)
    # the group loop and the micro-step scan, and inside each the expert
    # sort's searchsorted (a while of its own)
    assert lowered.as_text().count("stablehlo.while") == 4
    assert "ragged_paged_attention" in text and "grouped_matmul" in text
    for shape in ("bf16[8,8,1024,128]", "bf16[64,8,8,128]"):
        assert shape in text           # the kernel at 8 rows, and at 64
    assert _pool_copies(text, eng) == {"kv": (0, 0), "wkv": (0, 0)}
    _assert_pools_donated(text, mem, eng)


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


def test_linear_state_step_at_the_cells_shape(native, one_chip):
    """The one-step kernel under its no-delta rule (lightning attention) at
    a decode micro-step of the MiniCPM-SALA cell: 16 slots x 32 heads of
    128 x 128 float32 state written over its input, under its own kernel
    name."""
    from paddle_tpu.ops.pallas.gated_delta_step import linear_state_step
    s = _sds(one_chip)
    f32 = jnp.float32
    vec = s((16, 32, 128), f32)
    lowered = jax.jit(linear_state_step, donate_argnums=(0,)).lower(
        s((16, 32, 128, 128), f32), vec, vec, vec, s((16, 32), f32),
        s((16,), bool))
    assert "linear_state_step" in lowered.as_text()
    assert "gated_delta_step" not in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 16 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("b,s_", [(1, 2048), (16, 1)],
                         ids=["prefill_trip", "decode"])
def test_sparse_paged_attention_at_the_cells_shapes(native, one_chip, b, s_):
    """The block-sparse paged attention kernel at the MiniCPM-SALA cell's
    shapes: a prefill trip of 2,048 queries of one slot and a decode step of
    16, 32 / 2 heads x 128, pools of 16 x 528 pages of 64 keys, 64 blocks a
    sparse query (the block lists in SMEM, a KV group's page columns
    copied one page at a time, the copies it starts counted in SMEM)."""
    from paddle_tpu.ops.pallas.sparse_paged_attention import (
        sparse_paged_attention)
    from paddle_tpu.ops.sparse_select import SparseRule
    rule = SparseRule(32, 16, 64, 64, 1, 2048, 8192)
    s = _sds(one_chip)
    pool = s((16 * 528 + 1, 64, 256))
    text = _compile(
        lambda q, k, v, t, c, n, bl: sparse_paged_attention(
            q, k, v, t, c, n, bl, rule),
        s((b, s_, 32, 128)), pool, pool, s((b, 528), jnp.int32),
        s((b,), jnp.int32), s((b,), jnp.int32),
        s((b, s_, 2, 64), jnp.int32))
    assert "sparse_paged_attention" in text


def test_serving_step_program_at_qwen3_next_widths(native, one_chip, topo,
                                                   monkeypatch):
    """The ONE step program of a 4-layer model at Qwen3-Next's widths
    (hidden 2048; three delta-rule layers of 32 x 128 x 128 state beside one
    gated attention layer of 16 / 2 heads x 256; 64 held experts of 512 x
    512): per-slot float32 state beside host-managed pages, 64 slots x
    5120. It compiles for the described chip with the attention kernel in
    both loops, the one-step delta-rule kernel in the decode scan and the
    chunked form (its triangular solve) in the group loop. The pools are
    donated: no copy of a K/V pool or of a 134 MB state anywhere."""
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
    lowered, text, mem = _lower_step(model, eng, one_chip, topo,
                                     monkeypatch)
    for kernel in ("ragged_paged_attention", "grouped_matmul",
                   "gated_delta_step"):
        assert kernel in text
    for shape in ("bf16[8,2,1024,256]", "bf16[64,2,8,256]"):
        assert shape in text           # the attention kernel at 8 rows, at 64
    # ONE lowered body of the step kernel for the three delta-rule layers,
    # called from the decode scan alone
    assert lowered.as_text().count("gated_delta_step") == 1
    copies = _pool_copies(text, eng)
    assert set(copies) == {"kv", "state"} and copies["kv"] == (0, 0), copies
    # every state copy is of a 3 MB conv tail, none of a 134 MB state: XLA
    # keeps ``[64, 3, 8192]`` slot-major in one loop and tap-major in the
    # other, so the three tails are re-laid out at the loops' edges and
    # once a micro-step (as many as before the pools were donated)
    import re
    assert not re.findall(r"= f32\[64,32,128,128\]\S* copy\(", text)
    assert copies["state"] == (9, 3), copies
    _assert_pools_donated(text, mem, eng)


def test_serving_step_program_at_minicpm_sala_widths(native, one_chip, topo,
                                                     monkeypatch):
    """The ONE step program of a 2-layer model at MiniCPM-SALA's widths
    (hidden 4096, a lightning layer of 32 heads x 128 x 128 float32 state
    beside a block-sparse layer of 32 / 2 heads x 128, SwiGLU 16384, the
    whole 73,448-row vocabulary), 16 slots x 33,792 positions, page 64,
    prefill chunk 2,048 (one row a trip): per-slot state and a compressed-
    key index beside host-managed pages. It compiles for the described chip
    with the sparse kernel in both loops and the no-delta step kernel in the
    decode scan, fits the chip with its weights and under 1 GB of
    temporaries, and copies no K/V pool."""
    import re
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models import MiniCpmSalaConfig, MiniCpmSalaForCausalLM
    cfg = MiniCpmSalaConfig.minicpm_sala_9b()
    cfg.num_hidden_layers = 2
    cfg.mixer_types = ("lightning-attn", "minicpm4")
    cfg.first_published_layer = 14
    cfg.dtype, cfg.empty_init = "bfloat16", True
    model = MiniCpmSalaForCausalLM(cfg)
    model.eval()
    eng = ContinuousBatchingEngine(model, num_slots=16, max_len=33792,
                                   page_size=64, prefill_chunk=2048,
                                   decode_chunk=16, greedy=True)
    assert [tuple(p._data.shape) for p in eng.pools] \
        == [(16, 32, 128, 128)] + [(16 * 528 + 1, 64, 256)] * 2 \
        + [(16, 2112, 256), (4,)]
    lowered, text, mem = _lower_step(model, eng, one_chip, topo,
                                     monkeypatch, chunks=(1, 2048, 16))
    for kernel in ("sparse_paged_attention", "linear_state_step"):
        assert lowered.as_text().count(kernel) >= 1 and kernel in text
    copies = _pool_copies(text, eng)
    assert copies["kv"] == (0, 0), copies
    # the 33 MB state is laid out [d_v, d_k]-minor in the group loop (the
    # block form's transposed state) and [d_k, d_v]-minor in the scan (the
    # step kernel's): two copies a step at the loops' edges, none inside
    assert copies["state"] == (2, 0), copies
    assert len(re.findall(r"= f32\[16,32,128,128\]\S* copy\(", text)) == 2
    _assert_pools_donated(text, mem, eng)
    weights = sum(int(np.prod(p.shape)) * 2 for p in model.parameters())
    assert weights == 2 * (285_225_216 + 253_763_840 + 601_690_112)
    # the program's own temporaries beside weights and pools: the
    # selection's scores are tiled, a trip's activations are 2,048 rows
    assert mem.temp_size_in_bytes < 1 << 30, mem


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


@pytest.mark.parametrize("window", [1024, None], ids=["window", "causal"])
def test_flash_attention_at_8k_tokens(native, one_chip, window):
    """The Mellum training cell's attention: 2 x 8,192 tokens, 32 query
    heads over 4 KV heads of 128. Both take the blocked programs (the
    resident dkv would need 25 MB of scoped VMEM at this length)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    s = _sds(one_chip)
    text = _compile(_grad_sum(
        lambda q, k, v: flash_attention(q, k, v, True, None, window)),
        s((2, 8192, 32, 128)), s((2, 8192, 4, 128)), s((2, 8192, 4, 128)))
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in text


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


#: the training cell's loss tail: 4 x 1024 positions (4 x 1023 with a
#: target), GPT-2's width and vocabulary
_CE_N, _CE_D, _CE_V = 4092, 768, 50257


def _big_buffers(text, elements):
    """The f32/bf16 buffer shapes in ``text`` of ``elements`` or more."""
    import re
    return {m.group(0) for m in re.finditer(r"(f32|bf16)\[([\d,]+)\]", text)
            if np.prod([int(x) for x in m.group(2).split(",")]) >= elements}


@pytest.mark.parametrize("layout", ["vd", "dv"])
def test_linear_ce_fwd_bwd(native, one_chip, layout):
    """The fused head + CE pair at the training cell's shape, the f32
    master weight in either layout (``[V, D]`` tied, ``[D, V]``): the
    forward writes only [N] vectors, the backward one bf16 dlogits."""
    from paddle_tpu.ops.pallas.linear_ce import (_resolve_tiles, head_grads,
                                                 lse_and_target)
    s = _sds(one_chip)
    vf = layout == "vd"
    h = s((_CE_N, _CE_D))
    w = s((_CE_V, _CE_D) if vf else (_CE_D, _CE_V), jnp.float32)
    vec_i, vec_f = s((_CE_N,), jnp.int32), s((_CE_N,), jnp.float32)
    weight = {f"f32[{w.shape[0]},{w.shape[1]}]"}
    text = _compile(lambda h_, w_, lab: lse_and_target(h_, w_, lab, vf),
                    h, w, vec_i)
    assert "linear_ce_fwd" in text
    assert _big_buffers(text, _CE_N * 1024) == weight
    text = _compile(lambda h_, w_, lab, lse, sc: head_grads(
        h_, w_, lab, lse, sc, vf), h, w, vec_i, vec_f, vec_f)
    assert "linear_ce_dlogits" in text
    # besides the weight and dW: the dlogits, once, and the weight in
    # bf16 for XLA's dh matmul
    tm = _resolve_tiles(_CE_N, _CE_V)[0]
    dlogits = f"bf16[{-(-_CE_N // tm) * tm},{_CE_V}]"
    assert _big_buffers(text, _CE_N * 1024) <= weight | {
        dlogits, "bf16" + next(iter(weight))[3:]}


def test_gpt2_training_step_keeps_logits_in_vmem(native, one_chip,
                                                 monkeypatch):
    """GPT-2's labeled forward and backward under AMP O1 at the training
    cell's shapes (batch 4 x 1024, vocabulary 50,257, two layers): the
    fused kernels run, and no f32 array of 4,092 x 50,257 elements or
    more is left in the program."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models import GPT2Config, GPT2ForCausalLM

    cfg = GPT2Config(num_hidden_layers=2, hidden_dropout_prob=0.0,
                     attention_dropout_prob=0.0)
    assert cfg.vocab_size == _CE_V and cfg.hidden_size == _CE_D
    model = GPT2ForCausalLM(cfg)
    model.train()
    params = list(model.parameters())
    # the call sites ask which platform they run on: answer for the
    # described chip while the program is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss_fn(leaves, ids):
        held = [p._data for p in params]
        for p, a in zip(params, leaves):
            p._data = a
        try:
            with paddle.no_grad(), paddle.amp.auto_cast(level="O1",
                                                        dtype="bfloat16"):
                _, loss = model(Tensor(ids), labels=Tensor(ids))
            return loss._data
        finally:
            for p, a in zip(params, held):
                p._data = a

    s = _sds(one_chip)
    text = _compile(jax.value_and_grad(loss_fn),
                    [s(tuple(p.shape), p._data.dtype) for p in params],
                    s((4, 1024), jnp.int32))
    assert "linear_ce_fwd" in text and "linear_ce_dlogits" in text
    big = _big_buffers(text, _CE_N * _CE_V)
    assert not {b for b in big if b.startswith("f32")}, big


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


def test_held_expert_layer_backward_at_mellum_widths(native, one_chip):
    """The held-share layer differentiated as the Mellum training cell runs
    it: 16 held experts of 64, hidden 2304, expert width 896, 8 pairs a
    token over 2 x 8,192 tokens: forward, dx and dW over the live tiles."""
    from paddle_tpu.ops import moe

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(v, idx, w, wg, w1, w2):
        def loss(v_, w_, wg_, w1_, w2_):
            out, _ = moe.moe_experts_held(v_, idx, w_, w1_, w2_, 0,
                                          w_gate=wg_)
            return jnp.sum(out.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(v, w, wg, w1, w2)

    t = 2 * 8192
    text = _compile(fn, sds((t, 2304), BF16), sds((t, 8), jnp.int32),
                    sds((t, 8), jnp.float32), sds((16, 2304, 896), BF16),
                    sds((16, 2304, 896), BF16), sds((16, 896, 2304), BF16))
    assert "grouped_matmul_dw" in text


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
