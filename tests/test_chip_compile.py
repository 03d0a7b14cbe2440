"""Compile every Pallas kernel for a DESCRIBED TPU v5e, at the shapes
chip_smoke.py runs (rehearsal 3 of the on-chip-measurement guide).

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described and not attached, so what Mosaic refuses — a block shape
off the (8, 128) tiling, too much SMEM or VMEM — fails HERE, at no chip
time, instead of in the first chip run.  Interpret-mode tests cannot see
any of that.  A compile that passes is not a chip run: nothing executes.

``registry.select`` asks ``jax.default_backend()`` and would take its CPU
branch, so the tests call the Pallas functions themselves under
``jax.jit`` with ``ShapeDtypeStruct``s placed on the described device.

The topology is described inside module-scoped fixtures (never at import
time, in a ``skipif`` or in ``parametrize`` arguments): only one process
may load libtpu, every xdist worker imports every test file, and only the
worker that is handed THIS file may touch the library.  Keep these tests
in this one file, and compile in the test's own process.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.kernels import bn_act, flash_bwd, opt_arena
from mxnet_tpu.ops import attention as att

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``sds(shape, dtype)`` -> a ShapeDtypeStruct on chip 0 of the described
    host, with jax's persistent cache off around this module's compiles (an
    entry written for a described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    assert topo.devices[0].device_kind == "TPU v5 lite"
    one = SingleDeviceSharding(topo.devices[0])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def compile_kernel(fn, *args):
    """Lower + compile for the described chip; the kernel must be IN the
    program (a Mosaic custom call), not lowered away."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# --------------------------------------------------------------- decode path
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("slots,tq,cap", [
    (8, 1, 512),        # the smoke's decode step
    (1, 256, 512),      # its 256-token prefill chunk
    (8, 1, 256), (1, 64, 256),
    (64, 1, 1024), (8, 1, 40), (8, 1, 48),
], ids=lambda v: str(v))
def test_decode_kernel_float_cache(chip, dtype, slots, tq, cap):
    """The packed K‖V leaf at head size 64: one 128-lane operand."""
    q = chip((slots, 12, tq, 64), dtype)
    kv = chip((slots, 12, cap, 128), dtype)
    compile_kernel(
        lambda q, kv, n: att._decode_forward_pallas(q, kv, n, 0.125),
        q, kv, chip((slots,), I32))


@pytest.mark.parametrize("slots,tq,cap", [
    (8, 1, 512), (1, 256, 512), (8, 1, 256), (1, 64, 256),
    (8, 1, 40), (8, 1, 96),     # short capacities: one whole-axis block
], ids=lambda v: str(v))
def test_decode_kernel_int8_cache(chip, slots, tq, cap):
    """int8 K‖V pages + per-position f32 scales: the scale rows ride as
    (1, 1, bk) blocks over (B*H, 1, C) — a (1, bk) block over (B*H, C) is
    what Mosaic refused before PR 23."""
    q = chip((slots, 12, tq, 64), BF16)
    kv = chip((slots, 12, cap, 128), I8)
    sc = chip((slots, 12, cap, 1), F32)
    compile_kernel(
        lambda q, kv, n, ks, vs: att._decode_forward_pallas(
            q, kv, n, 0.125, k_scale=ks, v_scale=vs),
        q, kv, chip((slots,), I32), sc, sc)


@pytest.mark.parametrize("dh,dtype", [(128, BF16), (32, BF16), (128, I8)],
                         ids=["dh128", "dh32", "dh128_int8"])
def test_decode_kernel_other_head_sizes(chip, dh, dtype):
    """One layout for every decoder: a 256-lane leaf (dh 128) and a
    64-lane one (dh 32) take the same kernel, step and prefill chunk."""
    for slots, tq in ((8, 1), (1, 128)):
        scales = [chip((slots, 8, 512, 1), F32)] * (2 if dtype == I8 else 0)
        compile_kernel(
            lambda q, kv, n, ks=None, vs=None: att._decode_forward_pallas(
                q, kv, n, 0.125, k_scale=ks, v_scale=vs),
            chip((slots, 8, tq, dh), BF16),
            chip((slots, 8, 512, 2 * dh), dtype), chip((slots,), I32),
            *scales)


XL_LEAF = (16, 25, 1024, 128)       # gpt2-xl.batch-closed: K‖V at dh 64


@pytest.mark.parametrize("leaf", ["bf16", "bf16_lse", "int8"])
@pytest.mark.parametrize("slots,tq", [(16, 1), (16, 8), (1, 128), (1, 512)],
                         ids=lambda v: str(v))
def test_decode_kernel_at_the_xl_cell(chip, slots, tq, leaf):
    """``gpt2-xl.batch-closed``: the ``(16, 1)`` step in the step form —
    all 25 heads of a slot in one program, bf16 operands, 256-row kv
    blocks under the scalar-prefetched lengths — and its prefill chunks in
    the chunk form; ``return_lse`` and the int8 leaf in the same forms."""
    b, h, c, d2 = XL_LEAF
    hg, bq, bk = att._decode_form(h, tq, c, d2, I8 if leaf == "int8" else BF16)
    assert (hg, bk) == ((h, 256) if tq <= 8 else (1, 512))
    scales = [chip((slots, h, c, 1), F32)] * (2 if leaf == "int8" else 0)
    compile_kernel(
        lambda q, kv, n, ks=None, vs=None: att._decode_forward_pallas(
            q, kv, n, 0.125, return_lse=leaf == "bf16_lse",
            k_scale=ks, v_scale=vs),
        chip((slots, h, tq, d2 // 2), BF16),
        chip((slots, h, c, d2), I8 if leaf == "int8" else BF16),
        chip((slots,), I32), *scales)


def test_decode_step_keeps_the_cache_in_place(chip):
    """Two layers of the XL ``(16, 1)`` step's cache traffic, the leaves
    donated: each layer appends its new rows and attends.  The compiled
    program must alias every byte of the cache and hold no ``copy`` or
    ``transpose`` that produces a whole leaf — the (B, H, C, 64) pair of
    leaves had four such copies here and 96 in the 48-layer step (the
    chip's trace counted 192 copy events a step), each reading and
    writing 105 MB (PERF.md section 5)."""
    import re

    def step(caches, new_rows, q, lens):
        outs, new = [], []
        for kv, rows in zip(caches, new_rows):
            kv = att.cache_append(kv, rows, lens)
            outs.append(att._decode_forward_pallas(q, kv, lens, 0.125))
            new.append(kv)
        return outs, new

    b, h, c, d2 = XL_LEAF
    caches = [chip(XL_LEAF, BF16)] * 2
    rows = [chip((b, h, 1, d2), BF16)] * 2
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        caches, rows, chip((b, h, 1, d2 // 2), BF16),
        chip((b,), I32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    leaf = "bf16[%s]" % ",".join(map(str, XL_LEAF))
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose)\(" % re.escape(leaf),
                          ln)]
    assert not moved, moved
    cache_bytes = 2 * b * h * c * d2 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    # what the step holds besides its arguments is new rows and outputs,
    # not a second leaf (210 MB each)
    assert mem.temp_size_in_bytes < cache_bytes // 8


def test_row_cache_program_writes_the_tree_and_holds_nothing_else(chip):
    """An admission's fresh row cache at the XL cell's shapes, from the
    serve tier's own allocator block: one program that takes the capacity
    as a shape and writes 48 distinct ``bf16[1,25,1024,128]`` leaves —
    315 MB of outputs, no temporaries and no argument bytes (the
    reference is ``(capacity, 0)``): what it holds while it runs is the
    tree it hands out (XL's warm-up peaks at 16.65 of 16.91 GB, PERF.md
    section 6)."""
    from mxnet_tpu.gluon.model_zoo import transformer_lm
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.serve.decode import _CacheAllocator

    lm = transformer_lm(vocab_size=50257, units=1600, hidden_size=6400,
                        num_heads=25, num_layers=48, max_length=1024,
                        dtype="bfloat16")       # never initialised: shapes
    alloc = _CacheAllocator(lm.begin_cache)
    compiled = jax.jit(lambda ref: [
        [leaf._data for leaf in leaves]
        for leaves in alloc.forward(NDArray(ref))]).lower(
            chip((1024, 0), I32)).compile()
    leaves = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(leaves) == 48
    assert {(tuple(l.shape), l.dtype) for l in leaves} == {
        ((1,) + XL_LEAF[1:], jnp.dtype(BF16))}
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 48 * 25 * 1024 * 128 * 2
    assert mem.output_size_in_bytes < 48 * 25 * 1024 * 128 * 2 + (1 << 20)
    assert mem.temp_size_in_bytes == 0
    assert mem.argument_size_in_bytes == 0
    assert mem.alias_size_in_bytes == 0         # nothing handed out twice


MELLUM_FULL = (16, 4, 8192, 256)    # mellum ide-mixed-closed: K‖V at dh 128
MELLUM_RING = (16, 4, 1536, 256)    # a window layer: 1024 + the 512 chunk


@pytest.mark.parametrize("slots,tq,window", [
    (16, 1, None), (16, 1, 1024), (1, 512, None), (1, 512, 1024),
    (1, 128, 1024)], ids=lambda v: str(v))
def test_decode_kernel_at_the_mellum_cell(chip, slots, tq, window):
    """``mellum2-12b-a2.5b.ide-mixed-closed``: 32 query heads on 4 KV heads
    of 128.  The ``(16, 1)`` step in the step form -- all 4 KV heads of a
    slot in one program, a KV head's 8 query heads as its 8 query rows,
    256-row kv blocks -- against the full leaf and against the ring with
    its window; the 512- and 128-query chunks in the chunk form, one query
    head a program."""
    leaf = MELLUM_FULL if window is None else MELLUM_RING
    rows, h = (8, 4) if tq == 1 else (tq, 1)
    assert att._decode_form(h, rows, leaf[2], 256, BF16) == \
        ((4, 8, 256) if tq == 1 else (1, tq, 512))
    compile_kernel(
        lambda q, kv, n: att._decode_forward_pallas(q, kv, n, 128 ** -0.5,
                                                    window=window),
        chip((slots, 32, tq, 128), BF16), chip((slots,) + leaf[1:], BF16),
        chip((slots,), I32))


@pytest.mark.parametrize("slots,tq", [(16, 1), (1, 512)],
                         ids=["step", "chunk"])
def test_mellum_programs_keep_rings_and_pages_in_place(chip, slots, tq):
    """Two window layers and a full one of the step's and of the 512-query
    chunk's cache traffic, the leaves donated: each layer appends its rows
    (a chunk onto the ring as two fixed-shape writes, wrapping where it
    must) and attends.  Every byte of the cache is aliased, no ``copy`` or
    ``transpose`` produces a whole leaf, and what the program holds beside
    its arguments is rows and outputs, not a second leaf."""
    import re

    def program(rings, pages, new_rows, q, lens):
        outs, new_r, new_p = [], [], []
        for kv, rows in zip(rings, new_rows):
            kv = att.cache_append(kv, rows, lens, ring=True)
            outs.append(att._decode_forward_pallas(q, kv, lens, 128 ** -0.5,
                                                   window=1024))
            new_r.append(kv)
        for kv in pages:
            kv = att.cache_append(kv, new_rows[0], lens)
            outs.append(att._decode_forward_pallas(q, kv, lens, 128 ** -0.5))
            new_p.append(kv)
        return outs, new_r, new_p

    ring, full = ((slots,) + MELLUM_RING[1:], (slots,) + MELLUM_FULL[1:])
    compiled = jax.jit(program, donate_argnums=(0, 1)).lower(
        [chip(ring, BF16)] * 2, [chip(full, BF16)],
        [chip((slots, 4, tq, 256), BF16)] * 2,
        chip((slots, 32, tq, 128), BF16), chip((slots,), I32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3
    shapes = tuple("bf16[%s]" % ",".join(map(str, s)) for s in (ring, full))
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if any(re.search(r"= %s\S* (copy|transpose)\(" % re.escape(sh),
                              ln) for sh in shapes)]
    assert not moved, moved
    cache_bytes = 2 * slots * 4 * 256 * (2 * 1536 + 8192)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < cache_bytes // 8


OURO_LEAF = (8, 16, 384, 256)       # ouro math-closed: K‖V at dh 128, g = 1


@pytest.mark.parametrize("slots,tq", [(8, 1), (1, 64), (1, 128)],
                         ids=lambda v: str(v))
def test_decode_kernel_at_the_ouro_cell(chip, slots, tq):
    """``ouro-2.6b.math-closed``: 16 query heads on 16 KV heads of 128
    against a leaf of 384 rows, which no 256-row block divides.  The
    ``(8, 1)`` step in the step form -- all 16 heads of a slot in one
    program, three kv blocks of 128 rows -- and the 64- and 128-query
    prefill pieces in the chunk form, one head a program."""
    assert att._decode_form(16, tq, 384, 256, BF16) == \
        ((16, 8, 128) if tq == 1 else (1, tq, 128))
    compile_kernel(
        lambda q, kv, n: att._decode_forward_pallas(q, kv, n, 128 ** -0.5),
        chip((slots, 16, tq, 128), BF16), chip((slots,) + OURO_LEAF[1:], BF16),
        chip((slots,), I32))


@pytest.mark.parametrize("slots,tq", [(8, 1), (1, 128)],
                         ids=["step", "chunk"])
def test_ouro_programs_keep_every_pass_s_cache_in_place(chip, monkeypatch,
                                                        slots, tq):
    """The served programs themselves -- ``_DecodeStepper`` over ``OuroLM``
    for the step, the LM for a 128-token prefill piece -- at the published
    widths and the cell's slots and capacity, the stack cut to two layers
    (the loop is what is held: 4 passes x 2 layers = 8 K‖V leaves, the
    same two layers' weights in every pass), lowered from the program's
    own ``_CachedOp`` with shapes for parameters and arguments.  Every byte
    of the cache is aliased, no ``copy`` or ``transpose`` produces a whole
    leaf, each of the 8 layer applications holds its kernel, and what the
    program holds beside its arguments is rows and activations, not a
    second leaf."""
    import json
    import re

    from mxnet_tpu.gluon.block import _CachedOp
    from mxnet_tpu.gluon.model_zoo import get_model
    from mxnet_tpu.kernels import registry
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu.serve.decode import _DecodeStepper

    def hollow(sds):
        nd = NDArray.__new__(NDArray)
        nd._data = chip(sds.shape, sds.dtype)
        nd._grad = nd._grad_req = nd._autograd_entry = None
        return nd

    # the program asks jax for its backend and would take its CPU branch
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    monkeypatch.setenv("MXNET_COMPILE_CACHE", "0")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    config.update(num_hidden_layers=2, layer_types=["full_attention"] * 2)
    lm = get_model("ouro", config=config)           # never initialised
    for p in lm.collect_params().values():
        p._data = hollow(jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype)))
    cap = OURO_LEAF[2]
    cache = tuple(tuple(hollow(l) for l in ls) for ls in jax.eval_shape(
        lambda: [[l._data for l in ls] for ls in lm.begin_cache(slots, cap)]))
    i32 = lambda *shape: hollow(jax.ShapeDtypeStruct(shape, I32))
    if tq == 1:
        lm.hybridize(donate_args=(1,))
        block = _DecodeStepper(lm).hybridize(donate_args=(2,))
        args = (i32(slots), i32(4, slots), cache)
    else:
        block = lm.hybridize(donate_args=(1,))
        args = (i32(1, tq), cache, i32(1), i32(1))
    _, jit_fn, inputs, _ = _CachedOp(block)._prepare(args, False)
    compiled = jit_fn.lower(*(x._data for x in inputs)).compile()
    text = compiled.as_text()
    assert len(cache) == 8
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    leaf = "bf16[%s]" % ",".join(map(str, (slots,) + OURO_LEAF[1:]))
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose)\(" % re.escape(leaf), ln)]
    assert not moved, moved
    cache_bytes = 8 * slots * 16 * cap * 256 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    # a step holds less than ONE leaf beside its arguments (3.5 MB of 25);
    # a piece's activations and logits (7 MB) stay under half its row cache
    assert mem.temp_size_in_bytes < cache_bytes // (8 if tq == 1 else 2)


KIMI_STATE = (32, 32, 128, 128)     # kimi-linear reason-closed: a KDA state
KIMI_HELD = (16, 2304, 1024)        # 16 held experts of width 1024


@pytest.mark.parametrize("lanes,copies", [(640, 0), (576, 4)],
                         ids=["lane_dense_640", "published_576"])
def test_kimi_step_keeps_both_kinds_of_cache_in_place(chip, lanes, copies):
    """Two KDA and two MLA layers of the ``(32, 1)`` step's cache traffic,
    the leaves donated.  A latent leaf of 576 lanes (4.5 tiles) has two
    layouts in HBM and is copied whole between them twice a layer (113 MB
    each way); padded to 640 it has one, as the K‖V leaf above.  The f32
    state is read and written in place either way."""
    import re

    from mxnet_tpu.ops import kda, mla

    def step(states, latents, rows, qkvgb, q, w_kvb, lens):
        outs, new_s, new_l = [], [], []
        for s in states:
            s, o = kda.kda_step(*qkvgb, s)
            outs.append(o)
            new_s.append(s)
        for lat, r in zip(latents, rows):
            lat = att.cache_append(lat, r, lens)
            outs.append(mla.mla_absorbed(q, lat, lens, w_kvb, 128, 128))
            new_l.append(lat)
        return outs, new_s, new_l

    b, h, d, _ = KIMI_STATE
    leaf = (b, 1, 3072, lanes)
    vec = chip((b, h, d), F32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        [chip(KIMI_STATE, F32)] * 2, [chip(leaf, BF16)] * 2,
        [chip((b, 1, 1, lanes), BF16)] * 2,
        (vec, vec, vec, vec, chip((b, h), F32)),
        chip((b, 1, 32, 192), BF16), chip((32 * 256, 512), BF16),
        chip((b,), I32)).compile()
    text = compiled.as_text()
    shapes = ("f32[%s]" % ",".join(map(str, KIMI_STATE)),
              "bf16[%s]" % ",".join(map(str, leaf)))
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if any(re.search(r"= %s\S* (copy|transpose)\(" % re.escape(sh),
                              ln) for sh in shapes)]
    assert len(moved) == copies, moved
    cache_bytes = 2 * (4 * b * h * d * d + 2 * b * 3072 * lanes)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes
    if not copies:
        assert mem.temp_size_in_bytes < cache_bytes // 16


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_kimi_state_products_are_float32_on_the_chip(chip, form):
    """Every matrix product of the KDA recurrence compiles with float32
    operands (``operand_precision={highest,highest}``): the chip's default
    rounds float32 operands to bf16, and would read the float32 state as
    bf16 in ``S^T k`` and ``S^T q`` every step (the configuration states a
    float32 recurrence; on the chip ``STATE_RTOL`` of
    chipbench/references/kimi_linear.py holds the same thing by value)."""
    import re

    from mxnet_tpu.ops import kda

    b, h, d, _ = KIMI_STATE
    if form == "step":
        vec = chip((b, h, d), F32)
        lowered = jax.jit(kda.kda_step, donate_argnums=(5,)).lower(
            vec, vec, vec, vec, chip((b, h), F32), chip(KIMI_STATE, F32))
    else:
        row = chip((1, 512, h, d), F32)
        lowered = jax.jit(kda.kda_chunk).lower(
            row, row, row, row, chip((1, 512, h), F32),
            chip((1, h, d, d), F32), chip((1,), I32))
    products = [ln.strip() for ln in lowered.compile().as_text().splitlines()
                if re.search(r"= \S+ (convolution|dot)\(", ln)]
    assert products
    loose = [ln[:200] for ln in products
             if "operand_precision={highest,highest}" not in ln]
    assert not loose, loose


@pytest.mark.parametrize("rows,held,routed,slot", [
    (32, KIMI_HELD, 256, None), (512, KIMI_HELD, 256, 32),
    (16, (16, 2304, 896), 64, None), (512, (16, 2304, 896), 64, 128)],
    ids=["kimi_step", "kimi_prefill", "mellum_step", "mellum_chunk"])
def test_held_experts_are_one_batch_of_products(chip, rows, held, routed,
                                                slot):
    """The held experts' part at the published widths, both cells: a step's
    rows through every held expert, a chunk's gathered into a slot an
    expert (with the dense form behind a conditional for an overflow) -- in
    both, batched products over the 16 experts and no grouped custom
    call."""
    from mxnet_tpu.parallel import moe

    e, d, h = held
    assert moe._slot_rows(rows, 8, routed) == slot
    compiled = jax.jit(
        lambda *a: moe.held_experts_ffn(*a, routed=routed)).lower(
        chip((rows, d), BF16), chip((rows, 8), F32), chip((rows, 8), I32),
        chip(held, BF16), chip(held, BF16), chip((e, h, d), BF16)).compile()
    text = compiled.as_text()
    assert "ragged" not in text
    assert ("conditional(" in text) == (slot is not None)
    dense = 3 * 2 * e * rows * d * h
    if slot is None:
        assert dense <= compiled.cost_analysis()["flops"] < 1.1 * dense
    else:                               # the gathered slots are an operand
        assert f"bf16[{e},{slot},{d}]" in text


# ------------------------------------------------------------ training path
FLASH_SHAPES = [
    ((32, 12, 128, 64), False, True),     # BERT-base b32 s128 + kv_len
    ((8, 12, 1024, 64), True, False),     # causal, 1k context
    ((128, 12, 128, 64), False, True),    # bert-base.pretrain-s128 itself
]
FLASH_IDS = ["bert_b32_s128", "causal_1k", "bert_cell_b128_s128"]


def _form(shape, dtype=BF16):
    _, h, t, d = shape
    return att._train_form(h, t, t, d, dtype)


@pytest.mark.parametrize("shape,causal,has_len", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_forward_with_lse(chip, shape, causal, has_len):
    """The forward every TRAINING step takes (it saves the row lse), in
    the form ``_train_form`` gives the shape: every head of a batch row
    at s128, four heads and 512-row blocks at 1k."""
    assert _form(shape) == ((12, 128, 128) if shape[2] == 128
                            else (4, 512, 512))
    q = chip(shape, BF16)
    compile_kernel(
        lambda q, k, v, n: att._flash_forward_pallas(
            q, k, v, causal, 0.125, kv_len=n, return_lse=True),
        q, q, q, chip(shape[:1], I32) if has_len else None)


def _custom_call_results(compiled):
    """The result types of the program's Mosaic custom calls."""
    return [line.split(" custom-call(")[0].split(" = ", 1)[1]
            for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]


@pytest.mark.parametrize("shape,causal,has_len", FLASH_SHAPES, ids=FLASH_IDS)
def test_flash_backward(chip, shape, causal, has_len):
    """The backward at the form the call site picks: ONE program a batch
    row at s128 (dq, dk, dv out of the dk/dv kernel), the dq and dk/dv
    kernels at bq = bk = 512 on the 1k causal case (the largest VMEM
    footprint).  The gradients leave the kernels in the arrays' dtype:
    no f32 gradient buffer exists in the program."""
    b, h, t, d = shape
    q = chip(shape, BF16)
    lse = chip(shape[:3], F32)
    hg, bq, bk = _form(shape)
    compiled = compile_kernel(
        lambda q, k, v, g, o, lse, n: flash_bwd.flash_attention_bwd_pallas(
            q, k, v, g, o, lse, n, causal, 0.125, bq=bq, bk=bk, hg=hg),
        q, q, q, q, q, lse, chip(shape[:1], I32) if has_len else None)
    calls = _custom_call_results(compiled)
    assert len(calls) == (1 if t == bq else 2), calls
    assert sum(c.count(f"bf16[{b},{t},{h * d}]") for c in calls) == 3, calls
    assert not any("f32[" in c for c in calls), calls


def test_no_head_transpose_is_left_around_the_kernels(chip, monkeypatch):
    """An attention layer as ``npx.multi_head_attention`` writes it — split
    the fused projection, view as ``(B, H, T, d)``, ``flash_attention``,
    view back — differentiated and compiled for the described chip: the
    kernels take and give ``(B, T, H*d)`` (``_to_lanes``), the model's
    transposes cancel against them, and no copy or transpose of an
    activation is left in the program (until PR 38 eight a layer: 6.5% of
    the BERT step, PERF.md section 6)."""
    from mxnet_tpu.kernels import registry as kreg

    monkeypatch.setattr(kreg, "_backend", lambda: "tpu")
    b, t, h, d = 128, 128, 12, 64
    e = h * d

    def loss(x, w, wo, lens):
        q, k, v = jnp.split(jnp.einsum("bte,ef->btf", x, w), 3, axis=-1)
        q, k, v = (a.reshape(b, t, h, d).transpose(0, 2, 1, 3)
                   for a in (q, k, v))
        o = att.flash_attention(q, k, v, kv_valid_length=lens)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, e)
        return jnp.einsum("bte,ef->btf", o, wo).astype(F32).sum()

    with kreg.override("pallas"):
        compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            chip((b, t, e), BF16), chip((e, 3 * e), BF16),
            chip((e, e), BF16), chip((b,), I32)).compile()
    text = compiled.as_text()
    assert len(_custom_call_results(compiled)) == 2      # forward, backward
    assert " transpose(" not in text
    assert " copy(" not in text


@pytest.mark.parametrize("hg", [6, 4, 2])
def test_flash_cell_shape_other_head_groups(chip, hg):
    """The divisors the microbenchmark walked (PERF.md section 6, PR 38),
    and the two kernels at one block: all of them compile."""
    shape = (128, 12, 128, 64)
    q, lse, n = chip(shape, BF16), chip(shape[:3], F32), chip((128,), I32)
    compile_kernel(lambda q, k, v, n: att._flash_forward_pallas(
        q, k, v, False, 0.125, kv_len=n, return_lse=True, hg=hg), q, q, q, n)
    compile_kernel(
        lambda q, k, v, g, o, lse, n: flash_bwd.flash_attention_bwd_pallas(
            q, k, v, g, o, lse, n, False, 0.125, bq=128, bk=128, hg=hg,
            one_program=False),
        q, q, q, q, q, lse, n)


@pytest.mark.parametrize("shape,causal", [
    ((128, 12, 128, 64), False), ((8, 12, 1024, 64), True),
], ids=["b128_s128", "causal_1k"])
def test_flash_forward_inference(chip, shape, causal):
    q = chip(shape, BF16)
    compile_kernel(lambda q, k, v: att._flash_forward_pallas(
        q, k, v, causal, 0.125), q, q, q)


@pytest.mark.parametrize("t", [16, 64, 384])
def test_flash_short_and_odd_sequences(chip, t):
    """What ``_select_kernel`` calls eligible must compile: a short axis
    rides as one whole-axis block, 384 as three 128-blocks."""
    q = chip((2, 4, t, 64), F32)
    lse = chip((2, 4, t), F32)
    n = chip((2,), I32)
    hg, blk, _ = att._train_form(4, t, t, 64, F32)
    assert blk in (t, 128) and hg == 4
    compile_kernel(lambda q, k, v, n: att._flash_forward_pallas(
        q, k, v, True, 0.125, kv_len=n, return_lse=True), q, q, q, n)
    compile_kernel(
        lambda q, k, v, g, o, lse, n: flash_bwd.flash_attention_bwd_pallas(
            q, k, v, g, o, lse, n, True, 0.125, bq=blk, bk=blk, hg=hg),
        q, q, q, q, q, lse, n)


def test_flash_lengths_fit_smem_at_large_batch(chip):
    """Per-row lengths at a large batch: as a (rows, 1) SMEM block every
    row padded to 512 B and the chip's 1 MiB of SMEM ran out past ~2k rows
    (RESOURCE_EXHAUSTED ... space=smem; B*H = 6144 rows rode until PR 38).
    The vector is 1-D and, a program holding a batch row's heads, (B,)."""
    q = chip((512, 12, 128, 64), BF16)
    compile_kernel(
        lambda q, k, v, n: att._flash_forward_pallas(
            q, k, v, False, 0.125, kv_len=n, return_lse=True),
        q, q, q, chip((512,), I32))


def test_ineligible_shapes_are_decided_before_the_call():
    """No described chip needed: a length that is neither a multiple of
    128 nor a short whole axis is a counted ineligibility, never a
    compile-time refusal inside a user's step."""
    assert att._kernel_block(576) == 0       # 64-blocks: lane rule breaks
    assert att._kernel_block(1000) == 0
    assert att._kernel_block(40) == 40
    assert att._kernel_block(1024) == 512


# ----------------------------------------------------------- optimizer arena
@pytest.mark.parametrize("variant", ["sgd", "momentum", "adam"])
def test_arena_update_resnet50_sized(chip, variant):
    """ResNet-50's ~25.6 M f32 parameters as one flat arena."""
    n = opt_arena.build_layout([(25_557_032,)]).padded
    arena = chip((n,), F32)
    states = [arena] * opt_arena.VARIANT_STATES[variant]
    compile_kernel(
        lambda g, lr, t, *st: opt_arena.arena_update(
            variant, g, list(st), lr, t, momentum=0.9),
        arena, chip((), F32), chip((), I32), *states)


# ------------------------------------------------------------- fused BN+ReLU
@pytest.mark.parametrize("rows,c", [(128 * 56 * 56, 64), (128 * 7 * 7, 2048)],
                         ids=["stage1_c64", "stage4_c2048"])
def test_bn_act_kernels(chip, rows, c):
    x = chip((rows, c), BF16)
    vec = chip((c,), BF16)
    br = bn_act.pick_row_block(rows)
    assert br > 0
    compile_kernel(lambda x: bn_act._stats_pallas(x, br, False), x)
    compile_kernel(lambda x, s, b: bn_act._apply_pallas(
        x, s, b, "relu", br, False), x, vec, vec)
    compile_kernel(lambda x, g, b: bn_act.bn_act_train(
        x, g, b, 1e-5, "relu", False), x, chip((c,), F32), chip((c,), F32))


# ------------------------------------------------------- the four-chip mesh
@pytest.fixture(scope="module")
def mesh_sds(topo, chip):
    """``(mesh, sds)``: the described 2x2 host as a ``dp=4`` mesh, and
    ``sds(shape, dtype, spec)`` placing a shape on it."""
    import numpy as onp
    from jax.sharding import Mesh, NamedSharding

    mesh = Mesh(onp.array(topo.devices).reshape(4), ("dp",))
    return mesh, lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec))


def test_mosaic_kernels_are_not_auto_partitioned(mesh_sds):
    """Why ``kernels.registry.batch_mesh`` exists: under a multi-device
    GSPMD jit the compiler refuses a bare Pallas kernel — even with every
    operand replicated — and takes it per shard inside a shard_map."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.kernels import registry as kreg

    mesh, sds = mesh_sds

    def fwd(q, k, v, n):
        return att._flash_forward_pallas(q, k, v, False, 0.125, kv_len=n,
                                         return_lse=True)

    for spec in (P("dp"), P()):
        q, n = sds((32, 12, 128, 64), BF16, spec), sds((32,), I32, spec)
        with pytest.raises(Exception, match="cannot be automatically "
                                            "partitioned"):
            jax.jit(fwd).lower(q, q, q, n).compile()
    q, n = sds((32, 12, 128, 64), BF16, P("dp")), sds((32,), I32, P("dp"))
    with kreg.batch_mesh(mesh, "dp"):
        assert kreg.mesh_ineligible(32) is None
        assert "not divisible" in kreg.mesh_ineligible(30)
        compiled = compile_kernel(kreg.shard_over_batch(fwd), q, q, q, n)
    assert "all-gather" not in compiled.as_text()     # shard-local


@pytest.mark.parametrize("zero1", [True, False], ids=["zero1", "replicated"])
def test_arena_update_per_device_under_the_mesh(mesh_sds, zero1):
    """The trainer's arena update as it runs on a mesh: each device on its
    zero1 segment (whole sublane tiles by layout), or on the whole
    replicated arena."""
    import functools

    from jax.sharding import PartitionSpec as P

    mesh, sds = mesh_sds
    seg = P("dp") if zero1 else P()
    n = opt_arena.build_layout([(25_557_032,)], shard_multiple=4).padded
    arena = sds((n,), F32, seg)
    update = jax.shard_map(
        functools.partial(opt_arena.arena_update, "momentum", momentum=0.9),
        mesh=mesh, in_specs=(seg, seg, P(), P()), out_specs=seg,
        check_vma=False)
    compile_kernel(update, arena, [arena], sds((), F32, P()),
                   sds((), I32, P()))
