"""Hybridize/_CachedOp cache-invalidation edges
(ref tests/python/unittest/test_deferred_compute.py + CachedOp semantics,
src/imperative/cached_op.cc; round-3 verdict item #7).

The risk area: the jit cache must be keyed by everything that changes the
compiled graph (shape, dtype, train/eval mode) and must NOT bake in
anything that legitimately changes between calls (parameter VALUES,
RNG key, BatchNorm running stats).  Each test pins one edge.
"""
from __future__ import annotations

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn

np_ = mx.np


def N(x):
    return x.asnumpy() if hasattr(x, "asnumpy") else onp.asarray(x)


def _dense_net(units=3, in_units=4):
    net = nn.Dense(units)
    net.initialize(mx.init.Xavier())
    net(np_.ones((1, in_units)))  # shape-dependent deferred init
    return net


def _warm(net, *args):
    """First call after hybridize() runs eagerly (deferred-init warmup,
    block.py __call__); drive it so later calls hit the _CachedOp path."""
    net(*args)
    return net


def test_dtype_change_creates_new_entry_and_correct_output():
    net = _dense_net()
    net.hybridize()
    x32 = onp.random.RandomState(0).rand(2, 4).astype("float32")
    _warm(net, np_.array(x32))
    out32 = N(net(np_.array(x32)))
    before = len(net._cached_op._traced)
    out16 = N(net(np_.array(x32.astype("float16"))))
    assert len(net._cached_op._traced) == before + 1, \
        "dtype change must be a new jit signature"
    onp.testing.assert_allclose(out16.astype("float32"), out32,
                                rtol=2e-2, atol=2e-2)


def test_shape_change_reuses_params_not_graph():
    net = _dense_net()
    net.hybridize()
    w = N(net.weight.data())
    b = N(net.bias.data())
    for rows in (1, 2, 7):
        x = onp.random.RandomState(rows).rand(rows, 4).astype("float32")
        out = N(net(np_.array(x)))
        onp.testing.assert_allclose(out, x @ w.T + b, rtol=1e-5, atol=1e-5)


def test_param_value_update_without_retrace():
    """set_data between calls: the compiled graph takes params as INPUTS,
    so new values flow through with zero retraces."""
    net = _dense_net()
    net.hybridize()
    x = onp.random.RandomState(1).rand(2, 4).astype("float32")
    _warm(net, np_.array(x))
    N(net(np_.array(x)))
    sigs = len(net._cached_op._traced)
    new_w = onp.full((3, 4), 0.5, "float32")
    new_b = onp.zeros(3, "float32")
    net.weight.set_data(np_.array(new_w))
    net.bias.set_data(np_.array(new_b))
    out = N(net(np_.array(x)))
    assert len(net._cached_op._traced) == sigs, "set_data must not retrace"
    onp.testing.assert_allclose(out, x @ new_w.T + new_b, rtol=1e-6)


def test_force_reinit_then_forward():
    net = _dense_net()
    net.hybridize()
    x = np_.ones((2, 4))
    a = N(net(x))
    mx.random.seed(99)
    net.initialize(mx.init.Xavier(), force_reinit=True)
    b = N(net(x))
    assert not onp.allclose(a, b), "reinit must change hybridized outputs"
    onp.testing.assert_allclose(
        b, onp.ones((2, 4)) @ N(net.weight.data()).T + N(net.bias.data()),
        rtol=1e-5, atol=1e-5)


def test_rehybridize_clears_cache():
    net = _dense_net()
    net.hybridize()
    _warm(net, np_.ones((2, 4)))
    net(np_.ones((2, 4)))
    cached = net._cached_op
    assert cached._traced
    net.hybridize()  # re-activation clears the executor state
    assert net._cached_op is None or not net._cached_op._traced
    out = N(net(np_.ones((2, 4))))
    onp.testing.assert_allclose(
        out, onp.ones((2, 4)) @ N(net.weight.data()).T + N(net.bias.data()),
        rtol=1e-5, atol=1e-5)


def test_hybridize_off_matches_on():
    net = _dense_net()
    x = onp.random.RandomState(2).rand(3, 4).astype("float32")
    eager = N(net(np_.array(x)))
    net.hybridize()
    jitted = N(net(np_.array(x)))
    net.hybridize(False)
    eager2 = N(net(np_.array(x)))
    onp.testing.assert_allclose(eager, jitted, rtol=1e-6)
    onp.testing.assert_allclose(eager, eager2, rtol=1e-6)


def test_train_eval_mode_are_distinct_signatures():
    """Dropout must mask under record() and be identity in inference —
    the two modes are separate compiled graphs."""
    net = nn.HybridSequential()
    net.add(nn.Dense(16), nn.Dropout(0.5))
    net.initialize()
    net.hybridize()
    x = np_.ones((4, 8))
    _warm(net, x)
    infer = N(net(x))
    with mx.autograd.record(train_mode=True):
        train = N(net(x))
    # inference: no masking; training: ~half the activations zeroed
    assert (infer != 0).all()
    assert (train == 0).any()
    sigs = {k[0] for k in net._cached_op._traced}
    assert len(sigs) == 2, "train and eval must compile separately"


def test_batchnorm_running_stats_mutate_through_cache():
    net = nn.BatchNorm()
    net.initialize()
    net(np_.ones((2, 5)))
    net.hybridize()
    before = N(net.running_mean.data()).copy()
    rs = onp.random.RandomState(5)
    with mx.autograd.record(train_mode=True):
        for _ in range(3):
            net(np_.array(rs.rand(8, 5).astype("float32") + 2.0))
    after = N(net.running_mean.data())
    assert not onp.allclose(before, after), \
        "running stats must update through the jitted path"
    assert (after > 0.1).all()  # moved toward the +2 mean


def test_save_load_parameters_through_hybridized_net(tmp_path):
    net = _dense_net()
    net.hybridize()
    x = onp.random.RandomState(7).rand(2, 4).astype("float32")
    want = N(net(np_.array(x)))
    p = str(tmp_path / "dense.params")
    net.save_parameters(p)

    net2 = nn.Dense(3)
    net2.initialize()
    net2(np_.ones((1, 4)))
    net2.hybridize()
    _warm(net2, np_.array(x))
    N(net2(np_.array(x)))  # trace with old params first
    net2.load_parameters(p)
    got = N(net2(np_.array(x)))  # must reflect loaded params, no retrace
    onp.testing.assert_allclose(got, want, rtol=1e-6)


def test_child_block_replacement_recomputes_param_set():
    """Swapping a child after hybridize: the param cache must not serve
    the old structure (reference CachedOp rebuilds on structural change)."""
    class Outer(mx.gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            self.body = nn.Dense(3)

        def forward(self, x):
            return self.body(x)

    net = Outer()
    net.initialize()
    net(np_.ones((1, 4)))
    net.hybridize()
    N(net(np_.ones((2, 4))))
    net.body = nn.Dense(5)
    net.body.initialize()
    net.body(np_.ones((1, 4)))
    net.hybridize()  # structural change requires re-hybridize; cache resets
    out = net(np_.ones((2, 4)))
    assert out.shape == (2, 5)


def test_kwargs_in_hybrid_forward_raise():
    net = _dense_net()
    net.hybridize()
    _warm(net, np_.ones((2, 4)))
    net(np_.ones((2, 4)))
    with pytest.raises(mx.MXNetError):
        net._cached_op((np_.ones((2, 4)),), {"extra": 1})


def test_concurrent_shapes_interleaved():
    """Alternating signatures call-to-call: holders must not cross-talk."""
    net = _dense_net()
    net.hybridize()
    w, b = N(net.weight.data()), N(net.bias.data())
    xs = {s: onp.random.RandomState(s).rand(s, 4).astype("float32")
          for s in (1, 4)}
    for _ in range(4):
        for s, x in xs.items():
            onp.testing.assert_allclose(N(net(np_.array(x))),
                                        x @ w.T + b, rtol=1e-5, atol=1e-5)


def test_telemetry_compile_and_hit_counters_tick():
    """The jit cache is the #1 silent TPU cost: every trace must add
    compile seconds, every reuse must count as a hit (ISSUE 1 wiring)."""
    from mxnet_tpu import telemetry as tel

    prev = tel.set_enabled(True)
    tel.reset()
    try:
        net = _dense_net()
        net.hybridize()
        x = np_.ones((2, 4))
        _warm(net, x)
        N(net(x))                      # trace + compile (miss #1)
        snap = tel.snapshot()
        assert snap["hybridize.cache_misses"]["value"] == 1
        assert snap["hybridize.compile_seconds"]["count"] == 1
        assert snap["hybridize.compile_seconds"]["total"] > 0
        hits0 = snap.get("hybridize.cache_hits", {}).get("value", 0)
        for _ in range(3):
            N(net(x))                  # same signature: hits only
        snap = tel.snapshot()
        assert snap["hybridize.cache_hits"]["value"] == hits0 + 3
        assert snap["hybridize.cache_misses"]["value"] == 1
        N(net(np_.ones((5, 4))))       # new shape: one more miss
        snap = tel.snapshot()
        assert snap["hybridize.cache_misses"]["value"] == 2
        assert snap["hybridize.compile_seconds"]["count"] == 2
    finally:
        tel.reset()
        tel.set_enabled(prev)


# ---------------------------------------------------------------------------
# the compiled call is jax's: its cache decides hit or miss, and the
# signature is built on a miss alone (docs/jit.md, "The hybridize cache")
# ---------------------------------------------------------------------------

@pytest.fixture
def counts():
    """``(misses, hits)`` of the hybridize cache so far."""
    from mxnet_tpu import telemetry as tel

    prev = tel.set_enabled(True)
    tel.reset()

    def read():
        snap = tel.snapshot()
        return (snap.get("hybridize.cache_misses", {}).get("value", 0),
                snap.get("hybridize.cache_hits", {}).get("value", 0))

    try:
        yield read
    finally:
        tel.reset()
        tel.set_enabled(prev)


class _Traced(mx.gluon.HybridBlock):
    """A dense layer whose forward counts its runs: once per jax trace."""

    def __init__(self):
        super().__init__()
        self.body = nn.Dense(3, in_units=4)
        self.traces = 0

    def forward(self, x):
        self.traces += 1
        return self.body(x)


def _traced_net():
    net = _Traced()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    _warm(net, np_.ones((2, 4)))
    net.traces = 0
    return net


def test_compiled_call_builds_no_signature(monkeypatch, counts):
    from mxnet_tpu.gluon.block import _CachedOp

    net = _dense_net()
    net.hybridize()
    x = np_.ones((2, 4))
    _warm(net, x)
    N(net(x))                                     # the miss
    want = x.asnumpy() @ N(net.weight.data()).T + N(net.bias.data())

    def boom(*a):
        raise AssertionError("a compiled call built a signature")

    monkeypatch.setattr(_CachedOp, "_sig_of", staticmethod(boom))
    for _ in range(3):
        onp.testing.assert_allclose(N(net(x)), want, rtol=1e-6)
    assert counts() == (1, 3)


def test_new_shape_after_compiled_calls_traces_once(counts):
    net = _traced_net()
    for _ in range(3):
        N(net(np_.ones((2, 4))))
    assert net.traces == 1 and counts() == (1, 2)
    for _ in range(3):
        assert N(net(np_.ones((5, 4)))).shape == (5, 3)
    assert net.traces == 2 and counts() == (2, 4)
    assert len(net._cached_op._traced) == 2


@pytest.mark.parametrize("change", ["cast", "set_data_shape"])
def test_changed_state_is_a_miss(change, counts):
    net = _dense_net()
    net.hybridize()
    x = onp.random.RandomState(3).rand(2, 4).astype("float32")
    _warm(net, np_.array(x))
    N(net(np_.array(x)))
    N(net(np_.array(x)))
    assert counts() == (1, 1)
    if change == "cast":
        net.cast("float16")
        w, b = N(net.weight.data()), N(net.bias.data())
        assert w.dtype == onp.float16
    else:
        w = onp.full((5, 4), 0.25, "float32")
        b = onp.arange(5, dtype="float32")
        net.weight.set_data(np_.array(w))
        net.bias.set_data(np_.array(b))
    want = x @ w.astype("float32").T + b.astype("float32")
    for _ in range(2):
        onp.testing.assert_allclose(N(net(np_.array(x))).astype("float32"),
                                    want, rtol=2e-2, atol=2e-2)
    assert counts() == (2, 2)


def test_eval_shape_and_lint_lower_count_no_miss(monkeypatch, counts):
    from mxnet_tpu.analysis import xla_lint
    from mxnet_tpu.gluon import block as gblock

    net = _traced_net()
    x = np_.ones((3, 4))
    (aval,) = net.eval_shape(x)
    assert aval.shape == (3, 3) and net.traces == 1
    assert counts() == (0, 0)
    linted = []
    monkeypatch.setattr(xla_lint, "enabled", lambda: True)
    monkeypatch.setattr(gblock._xlint, "report", linted.append)
    co = net._cached_op
    _, jit_fn, inputs, holder = co._prepare((x,), False)
    co._lint_compiled(jit_fn, [i._data for i in inputs])
    assert linted and counts() == (0, 0)
    # the signature eval_shape traced compiles at its first call: a miss
    # there (jax's dispatch cache grows), and none after
    onp.testing.assert_allclose(N(net(x)).shape, (3, 3))
    assert counts() == (1, 0)
    N(net(x))
    assert counts() == (1, 1) and net.traces == 1


@pytest.mark.parametrize("state", ["batchnorm", "rng"])
def test_mutated_state_is_rebound(state, counts):
    mx.random.seed(4)
    if state == "batchnorm":
        net = nn.BatchNorm(in_channels=5)
        probe = lambda: N(net.running_mean.data()).copy()  # noqa: E731
    else:
        net = nn.HybridSequential()
        net.add(nn.Dense(32, in_units=8), nn.Dropout(0.5))
        probe = lambda: N(mx.random.key_holder()).copy()  # noqa: E731
    net.initialize()
    net.hybridize()
    x = np_.array(onp.random.RandomState(6).rand(4, 8 if state == "rng"
                                                 else 5).astype("f4") + 2.0)
    outs, seen = [], [probe()]
    with mx.autograd.train_mode():
        _warm(net, x)
        for _ in range(3):
            outs.append(N(net(x)))
            seen.append(probe())
    assert counts() == (1, 2)
    for a, b in zip(seen, seen[1:]):
        assert not onp.array_equal(a, b), "state must move every call"
    if state == "rng":
        assert not onp.array_equal(outs[1], outs[2]), "a fresh mask a call"


def test_donated_arguments_stay_donated(monkeypatch, counts):
    from mxnet_tpu.gluon import block as gblock

    # donation is off on the CPU backend while the persistent cache is armed
    monkeypatch.setattr(gblock._jit_cache, "ensure_cache", lambda: None)

    class Bump(mx.gluon.HybridBlock):
        def forward(self, state, x):
            return state + x

    net = Bump()
    net.hybridize(donate_args=(0,))
    _warm(net, np_.zeros(4), np_.ones(4))
    state = np_.zeros(4)
    for i in range(3):
        old, state = state, net(state, np_.ones(4))
        with pytest.raises(RuntimeError):
            old.asnumpy()                   # XLA took its buffer
    onp.testing.assert_allclose(N(state), 3.0)
    (holder,) = net._cached_op._holders.values()
    assert holder["donate_argnums"] == (1,)     # after the RNG key
    assert counts() == (1, 2)


def test_hooks_fire_on_compiled_calls(counts):
    net = _dense_net()
    net.hybridize()
    x = np_.ones((2, 4))
    _warm(net, x)
    seen = []
    net.register_forward_pre_hook(lambda b, a: seen.append(("pre", a[0])))
    net.register_forward_hook(lambda b, a, o: seen.append(("post", o)))
    outs = [net(x) for _ in range(3)]
    assert counts() == (1, 2)
    assert [k for k, _ in seen] == ["pre", "post"] * 3
    assert all(v is x for k, v in seen if k == "pre")
    assert [v for k, v in seen if k == "post"] == outs


def test_symbolize_replays_the_last_calls_shapes(counts):
    """Shapes of the last call, compiled or not, and no argument held for
    them (the call's input dies with its last reference)."""
    import gc
    import weakref

    net = _dense_net()
    net.hybridize()
    _warm(net, np_.ones((2, 4)))
    for rows in (2, 5, 2, 5, 7):
        x = np_.ones((rows, 4))
        N(net(x))
        tree, specs = net._last_args_spec
        assert [tuple(s) for s, _ in specs] == [(rows, 4)]
        sym = net.symbolize()
        assert "data" in sym.list_arguments()
        params = {k: p.data() for k, p in net.collect_params().items()}
        assert sym.eval(data=np_.ones((rows, 4)), **params)[0].shape == \
            (rows, 3)
    assert counts() == (3, 2)
    gc.collect()
    gc.disable()
    try:
        x = np_.ones((5, 4))
        gone = weakref.ref(x)
        N(net(x))
        del x
        assert gone() is None
    finally:
        gc.enable()


def test_background_warmup_beside_compiled_calls_leaks_no_tracer():
    """A warm-up thread traces (parameters swapped to tracers under the
    trace lock) while this thread makes compiled calls, which take no lock
    but the state collection's: every call sees the real parameters."""
    import sys

    import jax

    net = nn.HybridSequential()
    for _ in range(4):
        net.add(nn.Dense(64, in_units=16 if not len(net) else 64,
                         activation="relu"))
    net.add(nn.Dense(8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = np_.array(onp.random.RandomState(9).rand(3, 16).astype("f4"))
    _warm(net, x)
    want = N(net(x))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        handle = net.warmup([(rows, 16) for rows in range(4, 28)],
                            background=True)
        calls = 0
        while not handle.done() or calls < 20:
            out = net(x)
            assert not isinstance(out._data, jax.core.Tracer)
            onp.testing.assert_allclose(N(out), want, rtol=1e-5)
            calls += 1
        assert handle.wait(120) == 24
    finally:
        sys.setswitchinterval(interval)
    for p in net.collect_params().values():
        assert not isinstance(p.data()._data, jax.core.Tracer)
    onp.testing.assert_allclose(N(net(np_.ones((6, 16)))).shape, (6, 8))
