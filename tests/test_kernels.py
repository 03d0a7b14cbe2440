"""mx.kernels — Pallas kernel layer: selection/fallback registry, the
flat-arena fused optimizer update, and the fused BN+activation kernels,
all validated under the pallas interpreter (no TPU needed).

Bit-accuracy gates from the kernels design (docs/kernels.md):
  * arena optimizer vs the per-param adapter: few-ULP for sgd/momentum,
    documented convergence-level tolerance for adam (same bar PR 6 set
    for the zero1 reduce-scatter reordering);
  * the arena step's lowered HLO contains no per-leaf concatenate/stack
    of params (the round-3 refutation of stack-based fusion must not
    sneak back in);
  * fused BN+act matches batch_norm_train + activation within the
    documented one-pass-variance tolerance, forward AND gradients.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kernels import bn_act as kbn
from mxnet_tpu.kernels import opt_arena as koa
from mxnet_tpu.kernels import registry as kreg
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer, _ArenaOptAdapter


def _counter(name):
    m = tel.snapshot().get(name)
    return 0 if m is None else m["value"]


# -- registry ----------------------------------------------------------------

def test_mode_default_off_on_cpu(monkeypatch):
    monkeypatch.delenv("MXNET_KERNELS", raising=False)
    assert kreg.mode() == "off"          # CPU backend: silent default
    assert kreg.select("opt_arena") is None


def test_mode_env_and_override(monkeypatch):
    monkeypatch.setenv("MXNET_KERNELS", "interpret")
    assert kreg.mode() == "interpret"
    assert kreg.select("bn_act") == "interpret"
    with kreg.override("off"):
        assert kreg.select("bn_act") is None
    assert kreg.mode() == "interpret"
    monkeypatch.setenv("MXNET_KERNELS", "bogus")
    with pytest.raises(MXNetError):
        kreg.mode()


def test_unknown_kernel_name_rejected():
    with pytest.raises(MXNetError):
        kreg.select("nope")


def test_platform_fallback_observable(monkeypatch):
    monkeypatch.setenv("MXNET_KERNELS", "pallas")
    kreg.reset_warned()
    before = _counter("kernels.fallbacks.opt_arena")
    with pytest.warns(RuntimeWarning, match="platform"):
        assert kreg.select("opt_arena") is None   # pallas needs a TPU
    assert _counter("kernels.fallbacks.opt_arena") == before + 1
    # once per (kernel, reason): the second miss ticks but stays silent
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert kreg.select("opt_arena") is None
    assert _counter("kernels.fallbacks.opt_arena") == before + 2


# -- flat-arena layout + kernel ----------------------------------------------

def test_arena_layout_offsets_and_padding():
    lay = koa.build_layout([(5, 3), (17,), (2, 2, 2)])
    assert lay.offsets == (0, 15, 32)
    assert lay.sizes == (15, 17, 8)
    assert lay.total == 40
    assert lay.padded % (koa.LANES * 64) == 0
    lay8 = koa.build_layout([(5, 3)], shard_multiple=8)
    assert lay8.padded % 8 == 0


@pytest.mark.parametrize("variant", ["sgd", "momentum", "adam"])
def test_arena_kernel_matches_imperative_kernel(variant):
    from mxnet_tpu.optimizer import _adam_kernel, _sgd_kernel

    rs = onp.random.RandomState(3)
    lay = koa.build_layout([(40,)])
    w = jnp.asarray(rs.rand(lay.padded).astype("f4")) - 0.5
    g = jnp.asarray(rs.rand(lay.padded).astype("f4")) - 0.5
    m = jnp.asarray(rs.rand(lay.padded).astype("f4")) * 0.1
    v = jnp.asarray(rs.rand(lay.padded).astype("f4")) * 0.1
    lr, t = 0.05, 3
    if variant == "sgd":
        d, st = koa.arena_update("sgd", g, [], lr, t, interpret=True)
        ref, _ = _sgd_kernel(w, g, jnp.zeros(()), lr, 0.0, 1.0, -1.0, 0.0,
                             has_mom=False)
        onp.testing.assert_allclose(onp.asarray(w + d), onp.asarray(ref),
                                    rtol=1e-6, atol=1e-7)
    elif variant == "momentum":
        d, (m2,) = koa.arena_update("momentum", g, [m], lr, t,
                                    momentum=0.9, interpret=True)
        ref_w, ref_m = _sgd_kernel(w, g, m, lr, 0.0, 1.0, -1.0, 0.9,
                                   has_mom=True)
        onp.testing.assert_allclose(onp.asarray(w + d), onp.asarray(ref_w),
                                    rtol=1e-6, atol=1e-7)
        onp.testing.assert_allclose(onp.asarray(m2), onp.asarray(ref_m),
                                    rtol=1e-6, atol=1e-7)
    else:
        d, (m2, v2) = koa.arena_update("adam", g, [m, v], lr, t,
                                       beta1=0.9, beta2=0.999, eps=1e-8,
                                       interpret=True)
        ref_w, ref_m, ref_v = _adam_kernel(w, g, m, v, lr, 0.0, 1.0, -1.0,
                                           0.9, 0.999, 1e-8, t)
        onp.testing.assert_allclose(onp.asarray(m2), onp.asarray(ref_m),
                                    rtol=1e-6, atol=1e-7)
        onp.testing.assert_allclose(onp.asarray(v2), onp.asarray(ref_v),
                                    rtol=1e-6, atol=1e-7)
        onp.testing.assert_allclose(onp.asarray(w + d), onp.asarray(ref_w),
                                    rtol=2e-5, atol=2e-6)


def test_arena_zero_padding_inert():
    """Zero grads over the padded tail must keep zero state and zero
    delta — the invariant zero1 segment sharding relies on."""
    lay = koa.build_layout([(10,)])
    g = jnp.zeros((lay.padded,), jnp.float32).at[:10].set(1.0)
    m = jnp.zeros((lay.padded,), jnp.float32)
    v = jnp.zeros((lay.padded,), jnp.float32)
    d, (m2, v2) = koa.arena_update("adam", g, [m, v], 0.1, 1,
                                   interpret=True)
    for arr in (d, m2, v2):
        assert not onp.asarray(arr[10:]).any()


# -- trainer integration ------------------------------------------------------

def _ce():
    def f(pred, y):
        logp = jax.nn.log_softmax(pred.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]

    return f


def _mlp():
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(32, activation="relu"),
            mx.gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 12)))
    return net


def _data(b=16, n=12):
    rs = onp.random.RandomState(0)
    return (onp.asarray(rs.rand(b, n), "f4"),
            onp.asarray(rs.randint(0, 10, size=(b,)), "i4"))


def _run(opt, fused_opt, partition="replicated", steps=8, mesh=None,
         grad_accum=1, **kw):
    with kreg.override("interpret" if fused_opt != "off" else "off"):
        tr = ShardedTrainer(
            _mlp(), _ce(), mesh=mesh or make_mesh({"dp": -1}),
            optimizer=opt, learning_rate=0.05, partition=partition,
            fused_opt=fused_opt, grad_accum=grad_accum, **kw)
        x, y = _data()
        losses = [float(tr.step(x, y, block=True)) for _ in range(steps)]
    return tr, losses


@pytest.mark.parametrize("opt,kw,tol", [
    ("sgd", {"momentum": 0.0}, 5e-7),
    ("sgd", {"momentum": 0.9}, 5e-7),
    ("nag", {"momentum": 0.9}, 5e-7),
    ("adam", {}, 2e-3),      # convergence-level: bias-correction pow/fusion
])                           # reassociation, documented in docs/kernels.md
def test_arena_trainer_parity(opt, kw, tol):
    _, ref = _run(opt, "off", **kw)
    tr, got = _run(opt, "arena", **kw)
    assert isinstance(tr._adapter, _ArenaOptAdapter)
    worst = max(abs(a - b) / max(abs(a), 1.0) for a, b in zip(ref, got))
    assert worst <= tol, (opt, worst)


def test_arena_zero1_parity_and_memory():
    mesh = make_mesh({"dp": 8})
    _, ref = _run("sgd", "off", momentum=0.9, mesh=mesh)
    tr_r, got_r = _run("sgd", "arena", momentum=0.9, mesh=mesh)
    tr_z, got_z = _run("sgd", "arena", partition="zero1", momentum=0.9,
                       mesh=mesh)
    for got in (got_r, got_z):
        worst = max(abs(a - b) / max(abs(a), 1.0)
                    for a, b in zip(ref, got))
        assert worst <= 1e-6, worst
    # the arena shards over dp as flat segments: bytes divide exactly
    assert tr_z.opt_state_bytes_per_device * 8 == \
        tr_r.opt_state_bytes_per_device
    # ...and the per-step delta-arena gather is billed, not hidden
    assert tr_z.param_gather_bytes == \
        tr_z._adapter.layout.padded * 4 * 7 // 8
    assert tr_r.param_gather_bytes == 0


def test_arena_grad_accum_parity():
    _, ref = _run("sgd", "off", momentum=0.9, grad_accum=2, steps=8)
    _, got = _run("sgd", "arena", momentum=0.9, grad_accum=2, steps=8)
    worst = max(abs(a - b) / max(abs(a), 1.0) for a, b in zip(ref, got))
    assert worst <= 1e-6, worst


def test_arena_aot_compile_and_step():
    with kreg.override("interpret"):
        tr = ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                            optimizer="sgd", momentum=0.9,
                            learning_rate=0.05, fused_opt="arena")
        x, y = _data()
        assert tr.compile((x, y)) == 1
        l0 = float(tr.step(x, y, block=True))
    assert onp.isfinite(l0)


def test_arena_no_param_concatenate_in_hlo():
    """The acceptance gate of the flat-arena design: params are sliced,
    never packed — the step HLO carries at most the single grad-arena
    concatenate (plus its AD dual), regardless of parameter count.
    Checked through the X003 rule (analysis/xla_lint.check_arena_program)
    — ONE implementation of the invariant, shared with the CI graph
    lint and the runtime hooks, not a hand-rolled text grep."""
    from mxnet_tpu.analysis import xla_lint

    with kreg.override("interpret"):
        tr = ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                            optimizer="sgd", momentum=0.9,
                            fused_opt="arena")
        x, y = _data()
        xb, yb = tr._put(x), tr._put(y)
        txt = tr._step_fn.lower(
            tr.pvals, tr.avals, tr._key, tr.opt_state, 1,
            jnp.float32(0.05), tr._scale_state, xb, yb).as_text()
    diags = xla_lint.check_arena_program(txt, name="mlp-arena-step")
    assert diags == [], [d.format() for d in diags]
    # the rule is live, not vacuous: a tighter budget must flag this
    # same program (it legitimately carries the pack + AD dual)
    assert [d.code for d in
            xla_lint.check_arena_program(txt, budget=0)] == ["X003"]


def test_arena_fallback_reasons():
    kreg.reset_warned()
    with kreg.override("interpret"):
        # lamb is norm-based: observable fallback to the per-param path
        before = _counter("kernels.fallbacks.opt_arena")
        with pytest.warns(RuntimeWarning, match="not arena-fusible"):
            tr = ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                                optimizer="lamb", learning_rate=0.01)
        assert not isinstance(tr._adapter, _ArenaOptAdapter)
        assert _counter("kernels.fallbacks.opt_arena") == before + 1
        # explicit request on an unsupported optimizer raises
        with pytest.raises(MXNetError, match="arena"):
            ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                           optimizer="lamb", fused_opt="arena")
    with kreg.override("off"):
        with pytest.raises(MXNetError, match="unavailable"):
            ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                           optimizer="sgd", fused_opt="arena")


def test_arena_checkpoint_roundtrip_and_layout_guard(tmp_path):
    with kreg.override("interpret"):
        tr, _ = _run("sgd", "arena", momentum=0.9, steps=3)
        f = str(tmp_path / "st.npz")
        tr.save_states(f)
        with onp.load(f) as z:
            # arena leaves checkpoint STRIPPED to layout.total: the pad
            # width is a dp-dependent storage detail, and save_states
            # promises restore onto any mesh shape
            assert z["opt/0"].shape == (tr._adapter.layout.total,)
        tr.load_states(f)                 # re-pads onto this layout
        x, y = _data()
        assert onp.isfinite(float(tr.step(x, y, block=True)))
        # a per-param checkpoint must not silently feed the arena kernel
        tr_off, _ = _run("sgd", "off", momentum=0.9, steps=1)
        f2 = str(tmp_path / "off.npz")
        tr_off.save_states(f2)
        with pytest.raises(MXNetError, match="layout"):
            tr.load_states(f2)


def test_arena_non_f32_params_fall_back():
    from mxnet_tpu.optimizer import create as opt_create
    from mxnet_tpu.parallel.trainer import _OptAdapter, _pick_adapter

    kreg.reset_warned()
    with kreg.override("interpret"):
        before = _counter("kernels.fallbacks.opt_arena")
        with pytest.warns(RuntimeWarning, match="non-f32"):
            a = _pick_adapter(opt_create("sgd"), None, all_f32=False)
        assert type(a) is _OptAdapter
        assert _counter("kernels.fallbacks.opt_arena") == before + 1
        with pytest.raises(MXNetError, match="non-f32"):
            _pick_adapter(opt_create("sgd"), "arena", all_f32=False)


def test_arena_sharded_params_fall_back():
    """mp/fsdp-sharded params must not auto-select the arena (the grad
    pack would gather them replicated) — observable fallback; explicit
    request raises."""
    from mxnet_tpu.parallel.trainer import fsdp_spec_fn

    kreg.reset_warned()
    with kreg.override("interpret"):
        with pytest.warns(RuntimeWarning, match="sharded"):
            tr = ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                                optimizer="sgd", momentum=0.9,
                                spec_fn=fsdp_spec_fn(min_size=1))
        assert not isinstance(tr._adapter, _ArenaOptAdapter)
        with pytest.raises(MXNetError, match="sharded"):
            ShardedTrainer(_mlp(), _ce(), mesh=make_mesh({"dp": -1}),
                           optimizer="sgd", momentum=0.9,
                           spec_fn=fsdp_spec_fn(min_size=1),
                           fused_opt="arena")


def test_per_param_trainer_rejects_arena_checkpoint(tmp_path):
    """The reverse layout direction: an arena checkpoint must not
    silently feed a per-param trainer (leaf counts differ)."""
    tr_arena, _ = _run("sgd", "arena", momentum=0.9, steps=1)
    f = str(tmp_path / "arena.npz")
    tr_arena.save_states(f)
    tr_off, _ = _run("sgd", "off", momentum=0.9, steps=1)
    with pytest.raises(MXNetError, match="layout"):
        tr_off.load_states(f)


# -- fused BN + activation ----------------------------------------------------

def test_bn_act_forward_matches_reference():
    from mxnet_tpu.ops import nn as onn

    rs = onp.random.RandomState(1)
    x = jnp.asarray(rs.rand(4, 4, 4, 16).astype("f4")) * 2 - 1
    gamma = jnp.asarray(rs.rand(16).astype("f4")) + 0.5
    beta = jnp.asarray(rs.rand(16).astype("f4")) - 0.5
    y, mean, var = kbn.bn_act_train(x, gamma, beta, 1e-5, "relu", True)
    ref, _, _ = onn.batch_norm_train(x, gamma, beta, jnp.zeros(16),
                                     jnp.ones(16), axis=-1)
    onp.testing.assert_allclose(onp.asarray(y),
                                onp.asarray(jax.nn.relu(ref)),
                                rtol=1e-5, atol=1e-5)
    x2 = onp.asarray(x).reshape(-1, 16)
    onp.testing.assert_allclose(onp.asarray(mean), x2.mean(0), atol=1e-6)
    onp.testing.assert_allclose(onp.asarray(var), x2.var(0), atol=1e-5)


def test_bn_act_gradients_match_reference():
    from mxnet_tpu.ops import nn as onn

    rs = onp.random.RandomState(2)
    x = jnp.asarray(rs.rand(2, 4, 4, 8).astype("f4")) * 2 - 1
    gamma = jnp.asarray(rs.rand(8).astype("f4")) + 0.5
    beta = jnp.asarray(rs.rand(8).astype("f4"))
    w = jnp.asarray(rs.rand(8).astype("f4"))

    def fused(x, g, b):
        y, _, _ = kbn.bn_act_train(x, g, b, 1e-5, "relu", True)
        return (y * w).sum()

    def ref(x, g, b):
        o, _, _ = onn.batch_norm_train(x, g, b, jnp.zeros(8), jnp.ones(8),
                                       axis=-1)
        return (jax.nn.relu(o) * w).sum()

    ga = jax.grad(fused, (0, 1, 2))(x, gamma, beta)
    gr = jax.grad(ref, (0, 1, 2))(x, gamma, beta)
    for a, b in zip(ga, gr):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=2e-4, atol=2e-4)


def test_batch_norm_act_train_dispatch_and_fallbacks():
    from mxnet_tpu.ops import nn as onn

    rs = onp.random.RandomState(4)
    gamma, beta = jnp.ones(8), jnp.zeros(8)
    rm, rv = jnp.zeros(8), jnp.ones(8)
    kreg.reset_warned()
    with kreg.override("interpret"):
        x = jnp.asarray(rs.rand(4, 4, 4, 8).astype("f4"))
        d0 = _counter("kernels.dispatches.bn_act")
        out, nm, nv = onn.batch_norm_act_train(x, gamma, beta, rm, rv,
                                               axis=-1)
        assert _counter("kernels.dispatches.bn_act") == d0 + 1
        # channel-first input: observable layout fallback, same numerics
        xc = jnp.moveaxis(x, -1, 1)
        with pytest.warns(RuntimeWarning, match="channel-last"):
            outc, _, _ = onn.batch_norm_act_train(xc, gamma, beta, rm, rv,
                                                  axis=1)
        onp.testing.assert_allclose(onp.asarray(jnp.moveaxis(outc, 1, -1)),
                                    onp.asarray(out), rtol=1e-5, atol=1e-5)
        # non-tileable row count: observable shape fallback
        x_odd = jnp.asarray(rs.rand(1, 3, 3, 8).astype("f4"))
        with pytest.warns(RuntimeWarning, match="tile-able"):
            onn.batch_norm_act_train(x_odd, gamma, beta, rm, rv, axis=-1)
    # kernels off: silent reference path, moving stats still blend
    out_off, nm_off, nv_off = onn.batch_norm_act_train(
        x, gamma, beta, rm, rv, axis=-1)
    onp.testing.assert_allclose(onp.asarray(out_off), onp.asarray(out),
                                rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(nm_off), onp.asarray(nm),
                                rtol=1e-5, atol=1e-6)


def test_batch_norm_relu_block_fused_matches_default():
    def run(mode):
        mx.random.seed(3)
        bn = mx.gluon.nn.BatchNormReLU(axis=-1)
        bn.initialize()
        x = mx.np.array(onp.random.RandomState(5)
                        .rand(4, 4, 4, 8).astype("f4"))
        with kreg.override(mode), mx.autograd.record(train_mode=True):
            out = bn(x)
        return out.asnumpy(), bn.running_mean.data().asnumpy()

    y_ref, rm_ref = run("off")
    y_fused, rm_fused = run("interpret")
    onp.testing.assert_allclose(y_fused, y_ref, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(rm_fused, rm_ref, rtol=1e-5, atol=1e-6)


def test_resnet_fused_bn_relu_variant_parity():
    def run(fused, mode):
        mx.random.seed(7)
        net = mx.gluon.model_zoo.vision.get_resnet(
            1, 18, thumbnail=True, classes=10, layout="NHWC",
            fused_bn_relu=fused)
        net.initialize(mx.init.Xavier())
        x = mx.np.array(onp.random.RandomState(9)
                        .rand(4, 8, 8, 3).astype("f4"))
        with kreg.override(mode), mx.autograd.record(train_mode=True):
            out = net(x)
        return out.asnumpy()

    ref = run(False, "off")
    assert run(True, "off").shape == ref.shape       # structure variant OK
    onp.testing.assert_allclose(run(True, "interpret"), run(True, "off"),
                                rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(run(True, "off"), ref, rtol=1e-5,
                                atol=1e-5)
    with pytest.raises(MXNetError, match="v1"):
        mx.gluon.model_zoo.vision.get_resnet(2, 18, fused_bn_relu=True)
    # a uniform config sweep may pass the kwarg as False to v2 — accepted
    mx.gluon.model_zoo.vision.get_resnet(2, 18, fused_bn_relu=False)


# -- stable device names -----------------------------------------------------

def _pallas_call_sites():
    """Every ``pl.pallas_call(...)`` under ``mxnet_tpu/ops/attention.py``
    and ``mxnet_tpu/kernels/*.py``: [("<file>#<n-th call>", ast.Call), ...]."""
    import ast
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(root, "mxnet_tpu", "ops", "attention.py")] + sorted(
        glob.glob(os.path.join(root, "mxnet_tpu", "kernels", "*.py")))
    sites = []
    for path in files:
        with open(path) as f:
            tree = ast.parse(f.read())
        calls = sorted((node for node in ast.walk(tree)
                        if isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "pallas_call"),
                       key=lambda node: node.lineno)
        # ids by position, so an edit above a call does not rename its test
        sites += [(f"{os.path.basename(path)}#{i}", node)
                  for i, node in enumerate(calls)]
    return sites


def _literal_name(call):
    import ast

    for kw in call.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


_SITES = _pallas_call_sites()


@pytest.mark.parametrize("where", [w for w, _ in _SITES])
def test_pallas_call_site_passes_a_literal_name(where):
    """A device trace names a kernel after its ``name=``; without one it
    is named after the jax function it was traced in, and a refactor
    renames it (PERF.md section 3, ``kernels.*_share``)."""
    call = dict(_SITES)[where]
    name = _literal_name(call)
    assert name, f"{where} (line {call.lineno}): pallas_call without a " \
        "literal name="
    assert name.replace("_", "").isalnum() and name == name.lower()


def test_pallas_call_names_are_distinct_and_the_readers_know_them():
    names = [_literal_name(call) for _, call in _SITES]
    assert len(names) == 7
    assert len(set(names)) == len(names), names
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_decode",
            "opt_arena"} <= set(names)


# -- the training attention's program form on the dispatch record (PR 38) ----

def test_traced_bert_step_records_the_program_form():
    """A traced BERT-tiny step says which form its executable got: the
    ``kernels.dispatch`` instants of the flash forward and backward carry
    ``hg``/``bq``/``bk`` (every head of a batch row a program: hg = 4 > 1)
    and, on the backward, ``one_program``; the ``kernels.form.*`` gauges
    hold the same for a telemetry snapshot."""
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, get_bert
    from mxnet_tpu.trace import recorder as tr

    mx.random.seed(0)
    net = BERTForPretrain(
        get_bert("bert_12_768_12", vocab_size=97, max_length=32,
                 num_layers=1, units=32, hidden_size=64, num_heads=4,
                 dropout=0.0), vocab_size=97)
    net.initialize(mx.init.Xavier())
    B, T, PP = 8, 16, 4            # a row a device of the virtual mesh
    rs = onp.random.RandomState(2)
    x = (rs.randint(0, 97, (B, T)).astype("int32"),
         onp.zeros((B, T), "int32"),
         rs.randint(T // 2, T + 1, (B,)).astype("int32"),
         rs.randint(0, T, (B, PP)).astype("int32"))
    y = (rs.randint(0, 97, (B, PP)).astype("int32"),
         rs.randint(0, 2, (B,)).astype("int32"))
    L = gloss.SoftmaxCrossEntropyLoss()

    def loss_fn(preds, yy):
        (scores, nsp), (mlm_l, nsp_l) = preds, yy
        return (L(mx.nd.NDArray(scores), mx.nd.NDArray(mlm_l))._data.mean()
                + L(mx.nd.NDArray(nsp), mx.nd.NDArray(nsp_l))._data.mean())

    was = tr.set_enabled(True)
    tr.reset()
    try:
        with kreg.override("interpret"):
            trainer = ShardedTrainer(net, loss_fn, mesh=make_mesh({"dp": -1}),
                                     optimizer="sgd", learning_rate=0.05,
                                     fused_opt="off")
            assert onp.isfinite(float(trainer.step(x, y, block=True)))
        seen = {}
        for ev in tr.events():
            if ev["name"] == "kernels.dispatch":
                seen.setdefault(ev["attrs"]["kernel"], ev["attrs"])
    finally:
        tr.set_enabled(was)
    fwd, bwd = seen["flash_attention"], seen["flash_attention_bwd"]
    for attrs in (fwd, bwd):
        assert attrs["mode"] == "interpret"
        assert (attrs["hg"], attrs["bq"], attrs["bk"]) == (4, T, T)
    assert bwd["one_program"] is True and "one_program" not in fwd
    assert _counter("kernels.form.flash_attention.hg") == 4
    assert _counter("kernels.form.flash_attention_bwd.hg") == 4
    assert _counter("kernels.form.flash_attention_bwd.one_program") == 1
