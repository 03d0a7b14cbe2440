"""mx.callback / mx.dlpack / mx.error / mx.name / mx.AttrScope parity
(ref python/mxnet/{callback,dlpack,error,name,attribute}.py)."""
from __future__ import annotations

import logging

import numpy as onp
import pytest

import mxnet_tpu as mx

np_ = mx.np


# ---------------------------------------------------------------------------
# dlpack
# ---------------------------------------------------------------------------

def test_dlpack_roundtrip_numpy():
    # numpy -> mx via the producer protocol (numpy's own from_dlpack
    # refuses readonly buffers, so the mx->numpy leg goes through torch
    # in test_dlpack_torch_interop instead)
    src = onp.arange(6, dtype="float32").reshape(2, 3)
    a = mx.nd.from_dlpack(src)
    onp.testing.assert_allclose(a.asnumpy(), src)
    assert mx.nd.array(src).__dlpack_device__()[0] in (1, 2)  # CPU kinds


def test_dlpack_torch_interop():
    import torch

    a = mx.nd.array(onp.arange(4, dtype="float32"))
    t = torch.from_dlpack(a)
    onp.testing.assert_allclose(t.numpy(), a.asnumpy())
    # torch -> mx
    src = torch.arange(5, dtype=torch.float32)
    b = mx.nd.from_dlpack(src)
    onp.testing.assert_allclose(b.asnumpy(), src.numpy())


def test_dlpack_capsule_api():
    a = mx.nd.array(onp.ones((3,), "float32"))
    cap = mx.nd.to_dlpack_for_read(a)
    b = mx.nd.from_dlpack(cap)
    onp.testing.assert_allclose(b.asnumpy(), onp.ones(3))


# ---------------------------------------------------------------------------
# error classes
# ---------------------------------------------------------------------------

def test_error_distill_known_and_unknown():
    e = mx.error.distill_error("ValueError: bad axis")
    assert isinstance(e, ValueError) and "bad axis" in str(e)
    e = mx.error.distill_error("SomethingWeird: boom")
    assert isinstance(e, mx.MXNetError)


def test_error_internal_hint():
    e = mx.error.InternalError("engine corrupted")
    assert "MXNet hint" in str(e)
    assert isinstance(e, mx.MXNetError)


def test_error_register_custom():
    @mx.error.register
    class CartError(mx.MXNetError):
        pass

    e = mx.error.distill_error("CartError: off the rails")
    assert isinstance(e, CartError)


# ---------------------------------------------------------------------------
# callbacks
# ---------------------------------------------------------------------------

class _FakeMetric:
    def __init__(self):
        self.resets = 0

    def get_name_value(self):
        return [("acc", 0.5)]

    def reset(self):
        self.resets += 1


def test_speedometer_logs_and_resets(caplog):
    sm = mx.callback.Speedometer(batch_size=4, frequent=2, auto_reset=True)
    metric = _FakeMetric()
    with caplog.at_level(logging.INFO):
        for nb in range(5):
            sm(mx.callback.BatchEndParam(epoch=0, nbatch=nb,
                                         eval_metric=metric, locals=None))
    assert any("samples/sec" in r.message for r in caplog.records)
    assert metric.resets >= 1


def test_log_train_metric(caplog):
    cb = mx.callback.log_train_metric(period=1, auto_reset=False)
    with caplog.at_level(logging.INFO):
        cb(mx.callback.BatchEndParam(epoch=1, nbatch=3,
                                     eval_metric=_FakeMetric(),
                                     locals=None))
    assert any("Train-acc" in r.message for r in caplog.records)


def test_do_checkpoint_saves(tmp_path):
    x = mx.sym.var("data")
    net = mx.sym.FullyConnected(x, num_hidden=3, name="fc")
    cb = mx.callback.do_checkpoint(str(tmp_path / "m"), period=2)
    args = {"fc_weight": mx.nd.array(onp.ones((3, 4), "float32")),
            "fc_bias": mx.nd.array(onp.zeros(3, "float32"))}
    cb(0, net, args, {})   # epoch 1: period 2 -> no file yet
    cb(1, net, args, {})   # epoch 2: saves
    assert (tmp_path / "m-symbol.json").exists()
    assert (tmp_path / "m-0002.params").exists()


def test_validation_metrics_callback(caplog):
    cb = mx.callback.LogValidationMetricsCallback()
    with caplog.at_level(logging.INFO):
        cb(mx.callback.BatchEndParam(epoch=2, nbatch=0,
                                     eval_metric=_FakeMetric(),
                                     locals=None))
    assert any("Validation-acc" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# name / attribute scopes
# ---------------------------------------------------------------------------

def test_prefix_scope_shapes_symbol_names():
    with mx.name.Prefix("enc_"):
        s = mx.sym.FullyConnected(mx.sym.var("x"), num_hidden=2)
    assert s._outputs[0][0].name.startswith("enc_")
    t = mx.sym.FullyConnected(mx.sym.var("y"), num_hidden=2)
    assert not t._outputs[0][0].name.startswith("enc_")


def test_name_manager_counts_per_hint():
    m = mx.name.NameManager()
    assert m.get(None, "fc") == "fc0"
    assert m.get(None, "fc") == "fc1"
    assert m.get(None, "conv") == "conv0"
    assert m.get("explicit", "fc") == "explicit"


def test_attr_scope_stamps_and_survives_json():
    with mx.AttrScope(group="encoder", lr_mult="0.1"):
        s = mx.sym.FullyConnected(mx.sym.var("d"), num_hidden=2,
                                  name="fca")
    assert s.attr("group") == "encoder"
    assert s.list_attr()["lr_mult"] == "0.1"
    # survives the nnvm-json round trip
    js = s.tojson()
    assert "__scope_group" in js
    # outside the scope: no stamping
    t = mx.sym.FullyConnected(mx.sym.var("d2"), num_hidden=2)
    assert t.attr("group") is None


def test_attr_scope_nesting_merges():
    with mx.AttrScope(a="1"):
        with mx.AttrScope(b="2"):
            s = mx.sym.var("v")
    attrs = s.list_attr()
    assert attrs["a"] == "1" and attrs["b"] == "2"


def test_attr_scope_rejects_non_string():
    with pytest.raises(mx.MXNetError):
        mx.AttrScope(group=3)


def test_symbol_execution_unaffected_by_scope_attrs():
    with mx.AttrScope(group="g"):
        x = mx.sym.var("data")
        y = mx.sym.FullyConnected(x, num_hidden=3, name="fcx")
    out = y.eval(data=mx.nd.array(onp.ones((2, 4), "float32")),
                 fcx_weight=mx.nd.array(onp.ones((3, 4), "float32")),
                 fcx_bias=mx.nd.array(onp.zeros(3, "float32")))
    res = out[0] if isinstance(out, (list, tuple)) else out
    onp.testing.assert_allclose(res.asnumpy(), onp.full((2, 3), 4.0))


# ---------------------------------------------------------------------------
# np/npx surface completions (ref numpy/multiarray.py round_/
# triu_indices_from, numpy_extension/utils.py + random.py)
# ---------------------------------------------------------------------------

def test_np_surface_completions():
    import io

    onp.testing.assert_allclose(
        mx.np.round_(mx.np.array([1.26]), 1).asnumpy(), [1.3], rtol=1e-5)
    r, c = mx.np.triu_indices_from(mx.np.ones((3, 3)), k=1)
    onp.testing.assert_array_equal(onp.asarray(r),
                                   onp.triu_indices(3, 1)[0])
    onp.testing.assert_array_equal(onp.asarray(c),
                                   onp.triu_indices(3, 1)[1])
    g = mx.np.genfromtxt(io.StringIO("1,2\n3,4"), delimiter=",")
    onp.testing.assert_allclose(g.asnumpy(), [[1.0, 2.0], [3.0, 4.0]])
    with pytest.raises(ValueError):
        mx.np.triu_indices_from(mx.np.ones((2, 2, 2)))


def test_npx_utils_surface(tmp_path):
    mx.npx.seed(3)
    a = mx.npx.bernoulli(0.5, size=(100,))
    assert set(onp.unique(a.asnumpy())) <= {0.0, 1.0}
    with pytest.raises(mx.MXNetError):
        mx.npx.bernoulli(0.5, logit=0.1)
    assert mx.npx.normal_n(0.0, 1.0, batch_shape=(4, 2)).shape == (4, 2)
    assert mx.npx.uniform_n(onp.zeros(3), 1.0,
                            batch_shape=(5,)).shape == (5, 3)
    d = mx.npx.from_numpy(onp.eye(2))
    f = str(tmp_path / "z.npz")
    mx.npx.savez(f, x=d, y=onp.ones(3))
    loaded = onp.load(f)
    assert loaded["x"].shape == (2, 2) and loaded["y"].shape == (3,)
    e = mx.npx.from_dlpack(mx.npx.to_dlpack_for_read(d))
    onp.testing.assert_allclose(e.asnumpy(), onp.eye(2))


# -- test_utils completions (ref python/mxnet/test_utils.py) ----------------

def test_check_symbolic_backward_dot():
    import numpy as onp
    from mxnet_tpu import test_utils as tu

    a = onp.random.RandomState(0).rand(3, 4).astype("float32")
    b = onp.random.RandomState(1).rand(4, 2).astype("float32")
    og = onp.ones((3, 2), "float32")
    grads = tu.check_symbolic_backward(
        lambda x, y: mx.np.dot(x, y), [a, b], og,
        [og @ b.T, a.T @ og], rtol=1e-4, atol=1e-5)
    assert len(grads) == 2


def test_assert_exception_and_same_array():
    import numpy as onp
    import pytest
    from mxnet_tpu import test_utils as tu

    tu.assert_exception(lambda: 1 / 0, ZeroDivisionError)
    with pytest.raises(AssertionError):
        tu.assert_exception(lambda: None, ValueError)
    x = mx.np.array(onp.ones((2, 2), "float32"))
    assert tu.same_array(x, x)
    assert tu.same_array(x, x.detach())     # second wrapper, same buffer
    assert not tu.same_array(x, mx.np.array(onp.ones((2, 2), "float32")))
    # probe is identity-based: no value disturbance at all
    assert float(x.asnumpy().sum()) == 4.0


def test_rand_sparse_ndarray_roundtrip():
    import numpy as onp
    from mxnet_tpu import test_utils as tu

    rsp, dense = tu.rand_sparse_ndarray((6, 4), "row_sparse", density=0.5)
    onp.testing.assert_allclose(rsp.todense().asnumpy(), dense, rtol=1e-6)
    csr, dense2 = tu.rand_sparse_ndarray((5, 7), "csr", density=0.3)
    onp.testing.assert_allclose(csr.todense().asnumpy(), dense2,
                                rtol=1e-6)
    assert (dense2 == 0).any()              # density actually applied
    # fresh draws differ call to call (global RNG, not a pinned seed)
    a, _ = tu.rand_sparse_ndarray((8, 8), "csr")
    b, _ = tu.rand_sparse_ndarray((8, 8), "csr")
    assert not onp.allclose(a.todense().asnumpy(),
                            b.todense().asnumpy())
    # isolated stream when requested
    r1, d1 = tu.rand_sparse_ndarray((4, 4), "csr",
                                    rng=onp.random.RandomState(3))
    r2, d2 = tu.rand_sparse_ndarray((4, 4), "csr",
                                    rng=onp.random.RandomState(3))
    onp.testing.assert_allclose(d1, d2)


def test_profiler_domain_and_rtc_gate():
    """mx.profiler.Domain factories (ref profiler.py Domain) and the
    CUDA-only mx.rtc surface raising a clear error."""
    d = mx.profiler.Domain("net")
    t = d.new_task("fwd")
    t.start(); t.stop()
    c = d.new_counter("steps")
    c.increment(2); c.decrement()
    d.new_marker("ckpt").mark()
    f = d.new_frame("f0")
    f.start(); f.stop()
    text = mx.profiler.dumps(reset=True)
    assert "net::fwd" in text and "net::steps" in text
    assert mx.profiler.Frame is mx.profiler.Task

    assert mx.rnd is mx.random
    import pytest
    with pytest.raises(mx.MXNetError):
        mx.rtc.CudaModule("__global__ void k() {}")
    with pytest.raises(mx.MXNetError):
        mx.rtc.CudaKernel(None, "k")


def test_profiler_direct_construction_carries_domain():
    """Task(domain, name) built directly prefixes the domain exactly
    like Domain.new_task (review finding round 4)."""
    d = mx.profiler.Domain("trainer")
    direct = mx.profiler.Task(d, "step")
    via_factory = d.new_task("step")
    assert direct.name == via_factory.name == "trainer::step"
    c = mx.profiler.Counter(d, "n")
    assert c.name == "trainer::n"


def test_rand_sparse_accepts_generator():
    from mxnet_tpu import test_utils as tu

    g = onp.random.default_rng(7)
    csr, dense = tu.rand_sparse_ndarray((4, 6), "csr", rng=g)
    onp.testing.assert_allclose(csr.todense().asnumpy(), dense, rtol=1e-6)


def test_check_symbolic_backward_length_guard():
    from mxnet_tpu import test_utils as tu

    with pytest.raises(AssertionError):
        tu.check_symbolic_backward(lambda x: x * 2.0,
                                   [onp.ones((2,), "float32")], None,
                                   [onp.ones(2), onp.ones(2)])


def test_misc_legacy_scheduler():
    """mx.misc legacy scheduler API (ref python/mxnet/misc.py)."""
    import pytest

    import mxnet_tpu as mx

    s = mx.misc.FactorScheduler(step=10, factor=0.5)
    s.base_lr = 1.0
    assert s(0) == 1.0
    assert s(10) == 0.5
    assert s(25) == 0.25
    with pytest.raises(ValueError):
        mx.misc.FactorScheduler(step=0)
    with pytest.raises(ValueError):
        mx.misc.FactorScheduler(step=5, factor=1.5)
    with pytest.raises(NotImplementedError):
        mx.misc.LearningRateScheduler()(1)
