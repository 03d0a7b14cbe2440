"""The 'pp' pipeline mesh axis (ISSUE 14): stage splitting, the GPipe
schedule, micro-batch accounting, and composition with zero1.

What must hold: (1) ``pipeline_atoms``/``split_stages`` partition a net
into contiguous, parameter-balanced stages and refuse nets with fewer
atoms than stages; (2) ``bubble_fraction`` matches the GPipe analytic
figure and is published as ``trainer.pp_bubble_fraction``; (3) the pp
trainer keeps the grad-accum CONTRACT — k ``step()`` calls per
optimizer update, placeholder losses while the window buffers, window
mean on the flush — so drivers cannot tell pp from plain grad-accum;
(4) unsupported shapes fail LOUDLY (tuple batches, mutating forwards,
nets whose forward is not the fold of their children); (5) a pp
checkpoint is stage-agnostic: it restores onto a pp-less mesh and
trains on in parity; (6) ``pipeline_apply_stages`` itself computes the
sequential fold on a bare 'pp' mesh.
"""
from __future__ import annotations

import numpy as onp
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import pipeline_atoms
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.pipeline import (bubble_fraction, split_stages,
                                         pipeline_apply_stages)
from mxnet_tpu.parallel.trainer import ShardedTrainer


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _mlp(seed=0):
    """3 Dense atoms — splits 2 ways with a non-trivial balance."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu", in_units=8))
    net.add(nn.Dense(32, activation="relu", in_units=64))
    net.add(nn.Dense(4, in_units=32))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))
    return net


def _batch(seed=0, n=16):
    rs = onp.random.RandomState(seed)
    return (rs.rand(n, 8).astype("float32"),
            rs.randint(0, 4, (n,)).astype("int32"))


def _pp_trainer(net=None, grad_accum=2, **kw):
    return ShardedTrainer(net or _mlp(), _ce,
                          mesh=make_mesh({"dp": 4, "pp": 2}),
                          optimizer="sgd", learning_rate=0.05,
                          momentum=0.9, partition="zero1",
                          grad_accum=grad_accum, **kw)


# ---------------------------------------------------------------------------
# splitter + schedule math
# ---------------------------------------------------------------------------

def test_pipeline_atoms_flatten_nested_sequentials():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=8))
    inner = nn.HybridSequential()
    inner.add(nn.Dense(8, in_units=8))
    inner.add(nn.Dense(8, in_units=8))
    net.add(inner)
    net.add(nn.Dense(4, in_units=8))
    atoms = pipeline_atoms(net)
    assert len(atoms) == 4
    assert all(isinstance(a, nn.Dense) for a in atoms)


def test_split_stages_balance_and_guards():
    net = _mlp()
    stages = split_stages(net, 2)
    assert len(stages) == 2
    assert sum(len(st.blocks) for st in stages) == 3
    assert all(len(st.blocks) >= 1 for st in stages)
    # weights 576 / 2080 / 132: the greedy cut tracks the cumulative
    # half-way target, so the heavy middle Dense lands in stage 0 and
    # only the light head remains for stage 1
    assert len(stages[0].blocks) == 2
    with pytest.raises(MXNetError, match="n_stages"):
        split_stages(net, 0)
    small = nn.HybridSequential()
    small.add(nn.Dense(4, in_units=8))
    small.initialize()
    small(mx.np.zeros((2, 8)))
    with pytest.raises(MXNetError, match="fewer stages"):
        split_stages(small, 2)


def test_bubble_fraction_analytic():
    assert bubble_fraction(1, 4) == 0.0
    assert bubble_fraction(2, 4) == pytest.approx(0.2)
    assert bubble_fraction(2, 3) == pytest.approx(0.25)
    assert bubble_fraction(4, 1) == pytest.approx(0.75)


def test_pipeline_apply_stages_folds_sequentially():
    """The schedule kernel on a bare 'pp' mesh: 4 constant-width stages
    multiplying by k+1 must fold to x·24 for every micro-batch."""
    from jax import shard_map

    mesh = make_mesh({"pp": 4}, devices=jax.devices()[:4])
    m, mb, w = 3, 2, 5
    x = jnp.arange(m * mb * w, dtype=jnp.float32).reshape((m, mb, w))
    calls = [lambda a, _k=k: a.reshape((a.shape[0], -1)) * (_k + 1.0)
             for k in range(4)]
    out = shard_map(
        lambda xl: pipeline_apply_stages(calls, xl, w, w),
        mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False)(x)
    onp.testing.assert_allclose(onp.asarray(out),
                                onp.asarray(x) * 24.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# loud refusals
# ---------------------------------------------------------------------------

def test_pp_trainer_rejects_too_few_atoms():
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize()
    net(mx.np.zeros((2, 8)))
    with pytest.raises(MXNetError, match="fewer stages"):
        _pp_trainer(net=net)


def test_pp_trainer_rejects_tuple_batches():
    tr = _pp_trainer()
    x, y = _batch()
    with pytest.raises(MXNetError, match="single-array"):
        tr.step((x, x), y)


def test_pp_trainer_rejects_mutating_forward():
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8))
    net.add(nn.BatchNorm())
    net.add(nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))
    tr = _pp_trainer(net=net)
    x, y = _batch()
    with pytest.raises(MXNetError, match="mutation-free"):
        for _ in range(tr.grad_accum):
            tr.step(x, y)


def test_pp_validate_rejects_non_fold_net():
    class Res(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.d1 = nn.Dense(8, activation="relu", in_units=8)
            self.d2 = nn.Dense(8, in_units=8)

        def forward(self, x):
            return self.d2(self.d1(x)) + x  # residual: NOT the child fold

    mx.random.seed(0)
    net = Res()
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))

    def mse(pred, y):
        return ((pred - y) ** 2).sum(axis=-1)

    tr = ShardedTrainer(net, mse, mesh=make_mesh({"dp": 4, "pp": 2}),
                        optimizer="sgd", learning_rate=0.05,
                        partition="zero1", grad_accum=2)
    x = onp.random.RandomState(0).rand(16, 8).astype("float32")
    with pytest.raises(MXNetError, match="does not reproduce"):
        tr.step(x, x)


# ---------------------------------------------------------------------------
# micro-batch accounting + parity + checkpointing
# ---------------------------------------------------------------------------

def test_pp_grad_accum_accounting():
    tr = _pp_trainer(grad_accum=3)
    x, y = _batch()
    losses = [float(tr.step(x, y, block=True)) for _ in range(6)]
    # buffered micros return placeholder 0; each 3rd call flushes the
    # window and returns its mean loss — exactly one update per window
    assert losses[0] == 0.0 and losses[1] == 0.0 and losses[3] == 0.0
    assert losses[2] > 0.0 and losses[5] > 0.0
    assert tr._t == 2
    assert tr._micro == 0
    snap = tel.snapshot()
    assert snap["trainer.pp_bubble_fraction"]["value"] == \
        pytest.approx(bubble_fraction(2, 3))


def test_pp_parity_with_replicated_trainer():
    """Identical micros make the window mean equal the batch loss, so a
    pp×zero1 grad-accum trainer must track a replicated dp-only trainer
    on a fixed batch (the spmd_smoke methodology, shortened)."""
    x, y = _batch()
    tr_ref = ShardedTrainer(_mlp(seed=7), _ce, mesh=make_mesh({"dp": 8}),
                            optimizer="sgd", learning_rate=0.05,
                            momentum=0.9, partition="replicated")
    tr_pp = _pp_trainer(net=_mlp(seed=7), grad_accum=2)
    for step in range(4):
        a = float(tr_ref.step(x, y, block=True))
        bs = [float(tr_pp.step(x, y, block=True))
              for _ in range(2)]
        b = bs[-1]
        assert abs(a - b) / max(abs(a), 1.0) < 1e-5, (step, a, b)


def test_pp_composes_with_bf16_amp():
    """The precision ladder's pp rung (ISSUE 20, docs/precision.md):
    the GPipe window runs bf16 compute via amp.trainer_kwargs() while
    master params stay f32, tracking the f32 replicated trainer at bf16
    resolution rather than ULP parity."""
    x, y = _batch()
    tr_ref = ShardedTrainer(_mlp(seed=9), _ce, mesh=make_mesh({"dp": 8}),
                            optimizer="sgd", learning_rate=0.05,
                            momentum=0.9, partition="replicated")
    mx.amp.init(target_dtype="bfloat16")
    tr_pp = _pp_trainer(net=_mlp(seed=9), grad_accum=2,
                        **mx.amp.trainer_kwargs())
    mx.amp.init_trainer(tr_pp)
    for step in range(4):
        a = float(tr_ref.step(x, y, block=True))
        b = [float(tr_pp.step(x, y, block=True)) for _ in range(2)][-1]
        # bf16 mantissa noise, not the 1e-5 of the f32 parity test
        assert abs(a - b) / max(abs(a), 1.0) < 5e-2, (step, a, b)
    assert tr_pp._t == 4
    assert all(v.dtype == jnp.float32 for v in tr_pp.pvals)


def test_pp_save_states_mid_window_raises(tmp_path):
    tr = _pp_trainer(grad_accum=2)
    x, y = _batch()
    tr.step(x, y)  # 1 of 2 micros pending
    with pytest.raises(MXNetError, match="pending"):
        tr.save_states(str(tmp_path / "mid.npz"))


def test_pp_checkpoint_is_stage_agnostic(tmp_path):
    """pp+zero1 state saves unsharded/unstaged and restores onto a
    pp-LESS mesh, where training continues in parity with the pp
    trainer it came from."""
    x, y = _batch()
    tr_pp = _pp_trainer(net=_mlp(seed=3), grad_accum=2)
    for _ in range(2):
        tr_pp.step(x, y, block=True)  # one full window
    fname = str(tmp_path / "pp.npz")
    tr_pp.save_states(fname)

    tr_dp = ShardedTrainer(_mlp(seed=11), _ce, mesh=make_mesh({"dp": 8}),
                           optimizer="sgd", learning_rate=0.05,
                           momentum=0.9, partition="zero1")
    tr_dp.load_states(fname)
    assert tr_dp._t == tr_pp._t
    for a, b in zip(tr_pp.pvals, tr_dp.pvals):
        onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(b))
    # both trainers continue from the checkpoint in parity
    for _ in range(3):
        la = [float(tr_pp.step(x, y, block=True)) for _ in range(2)][-1]
        lb = float(tr_dp.step(x, y, block=True))
        assert abs(la - lb) / max(abs(la), 1.0) < 1e-5
