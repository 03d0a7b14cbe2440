"""mx.analysis.xla_lint — executable graph lint (ISSUE 10).

The load-bearing claims under test: (1) the parser reads op mix /
aliasing / f64 / callback facts out of both compiled HLO and lowered
StableHLO; (2) each X rule fires on a SEEDED regression built from a
real executable (forced replicated opt state under zero1, forced extra
concatenate, dropped/unusable donation, injected f64, embedded host
callback) and stays silent on its clean twin; (3) the three compile
seams — ``_CachedOp``, ``ShardedTrainer.compile()``, serve
``Registry`` register warmup — run the pass under ``MXNET_XLA_LINT=1``
with per-rule telemetry, and ``=raise`` turns findings into MXNetError;
(4) the arena <=2-concatenate invariant is ONE implementation
(``check_arena_program``) shared by tests, smoke, and CI.
"""
from __future__ import annotations

import warnings

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry as tel
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.analysis import xla_lint as xl
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer


@pytest.fixture(autouse=True)
def _fresh_lint(monkeypatch):
    monkeypatch.delenv("MXNET_XLA_LINT", raising=False)
    xl.reset_warned()
    yield
    xl.reset_warned()


def _ce(pred, y):
    logp = jax.nn.log_softmax(pred.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]


def _mlp(units=64, seed=0):
    """units=64 keeps every param under MXNET_ZERO1_MIN_SIZE; the zero1
    tests use _big_mlp so state leaves are EXPECTED dp-sharded."""
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu", in_units=8))
    net.add(nn.Dense(4, in_units=units))
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))
    return net


def _big_mlp(seed=0):
    """First weight 512x8=4096 elements > the 2048-element zero1
    min-size: its optimizer state MUST be dp-sharded under zero1."""
    return _mlp(units=512, seed=seed)


def _batch(seed=0):
    rs = onp.random.RandomState(seed)
    return (rs.rand(16, 8).astype("float32"),
            rs.randint(0, 4, (16,)).astype("int32"))


# ---------------------------------------------------------------------------
# parser units (synthetic program text)
# ---------------------------------------------------------------------------

_HLO = """\
HloModule jit_f, is_scheduled=true, input_output_alias={ {}: (0, {}, \
may-alias), {1}: (3, {}, must-alias) }, entry_computation_layout=x

%fused (p0: f32[8,4], p1: f32[8,4]) -> f32[16,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[8,4]{1,0} parameter(1)
  %concatenate.0 = f32[16,4]{1,0} concatenate(%p0, %p1), dimensions={0}
  %ar = f64[16,4]{1,0} all-reduce-start(%concatenate.0), to_apply=%add
  %ar.1 = f64[16,4]{1,0} all-reduce-done(%ar)
  ROOT %t = (f32[16,4]{1,0}, f32[]) tuple(%ar.1, %p0)
}

ENTRY %main (Arg_0: f32[8,4]) -> f32[16,4] {
  %Arg_0 = f32[8,4]{1,0} parameter(0)
  %cc = f32[1]{0} custom-call(%Arg_0), \
custom_call_target="xla_python_cpu_callback"
  ROOT %ag = f32[16,4]{1,0} all-gather(%Arg_0), dimensions={0}
}
"""


def test_parse_compiled_hlo_facts():
    f = xl.parse_program_text(_HLO, name="synthetic")
    assert f.dialect == "hlo"
    assert f.op_counts["concatenate"] == 1
    # async start/done folds into ONE all-reduce
    assert f.op_counts["all-reduce"] == 1
    assert "all-reduce-start" not in f.op_counts
    assert f.op_counts["all-gather"] == 1
    # tuple-typed instruction parses (the type contains spaces)
    assert f.op_counts["tuple"] == 1
    assert f.aliased_params == {0, 3}
    assert f.f64_count == 2
    assert f.callback_targets == ["xla_python_cpu_callback"]
    assert f.collective_counts == {"all-gather": 1, "all-reduce": 1}


def test_parse_stablehlo_facts():
    txt = jax.jit(lambda a, b: jnp.concatenate([a, b])).lower(
        jnp.ones((4, 2)), jnp.ones((4, 2))).as_text()
    f = xl.parse_program_text(txt)
    assert f.dialect == "stablehlo"
    assert f.op_counts["concatenate"] == 1


def test_rule_catalog_has_x_series():
    from mxnet_tpu.analysis.diagnostics import RULES

    for code in ("X001", "X002", "X003", "X004", "X005", "X006", "X007"):
        assert code in RULES
        title, why, fix = RULES[code]
        assert title and why and fix


def test_sync_collective_counts_hlo():
    """op_counts folds async pairs into the base op, so it alone cannot
    tell an overlappable pair from a serializing sync op —
    sync_collective_counts records the blocking occurrences BEFORE the
    fold (X007's input)."""
    f = xl.parse_program_text(_HLO, name="synthetic")
    # the all-reduce is a -start/-done pair: folded, NOT sync
    assert f.sync_collective_counts.get("all-reduce", 0) == 0
    # the all-gather is a plain blocking op
    assert f.sync_collective_counts["all-gather"] == 1
    assert f.to_dict()["sync_collectives"] == {"all-gather": 1}


_WRAPPED_ASYNC_HLO = """\
HloModule jit_g, is_scheduled=true

%wrapped_reduce-scatter (p0: f32[16]) -> f32[2] {
  %p0 = f32[16]{0} parameter(0)
  ROOT %rs = f32[2]{0} reduce-scatter(%p0), dimensions={0}, to_apply=%add
}

ENTRY %main (Arg_0: f32[16]) -> f32[2] {
  %Arg_0 = f32[16]{0} parameter(0)
  %s = ((f32[16]), f32[2]) async-start(%Arg_0), \
calls=%wrapped_reduce-scatter
  ROOT %d = f32[2]{0} async-done(%s), calls=%wrapped_reduce-scatter
}
"""


def test_sync_counts_wrapped_async_form():
    """Collectives with no dedicated -start opcode (reduce-scatter,
    all-to-all) go async via the generic async-start wrapper calling a
    %wrapped_* computation — counted toward the base op, never as
    blocking."""
    f = xl.parse_program_text(_WRAPPED_ASYNC_HLO, name="wrapped")
    assert f.op_counts["reduce-scatter"] == 1
    assert "async-start" not in f.op_counts
    assert f.sync_collective_counts.get("reduce-scatter", 0) == 0


def test_sync_counts_stablehlo_dialect():
    """StableHLO has no async forms: every collective is blocking until
    the backend schedules it, so the lowered dialect reports them all
    in sync_collective_counts (spelled the HLO way)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({"dp": 8})
    txt = jax.jit(shard_map(
        lambda a: jax.lax.psum(a, "dp"), mesh=mesh,
        in_specs=P("dp"), out_specs=P())).lower(
            jnp.ones((8, 4))).as_text()
    f = xl.parse_program_text(txt)
    assert f.dialect == "stablehlo"
    assert f.sync_collective_counts["all-reduce"] >= 1
    assert f.sync_collective_counts["all-reduce"] == \
        f.op_counts["all-reduce"]


def test_x007_fires_on_sync_only_under_async_budget():
    base = {"allow_f64": True, "allow_callbacks": True}
    f = xl.parse_program_text(_HLO)
    # no async_required -> disengaged even with the sync all-gather
    assert [d.code for d in xl.run_rules(f, dict(base))] == []
    # the async all-reduce satisfies its contract; the sync all-gather
    # violates its own
    diags = xl.run_rules(f, dict(
        base, async_required=["all-reduce", "all-gather"]))
    assert [d.code for d in diags] == ["X007"]
    assert "all-gather" in diags[0].message
    # wrapped-async reduce-scatter is clean under the same contract
    g = xl.parse_program_text(_WRAPPED_ASYNC_HLO)
    assert [d.code for d in xl.run_rules(
        g, dict(base, async_required=["reduce-scatter"]))] == []


# ---------------------------------------------------------------------------
# rule semantics on synthetic facts
# ---------------------------------------------------------------------------

def test_x002_surprise_vs_over_budget_vs_unbudgeted():
    f = xl.parse_program_text(_HLO)
    # no collectives key -> X002 disengaged entirely
    assert [d.code for d in xl.run_rules(
        f, {"allow_f64": True, "allow_callbacks": True})] == []
    # empty budget: every collective is a surprise
    codes = [d.code for d in xl.run_rules(
        f, {"collectives": {}, "allow_f64": True, "allow_callbacks": True})]
    assert codes == ["X002", "X002"]
    # exact budget: clean
    assert [d.code for d in xl.run_rules(
        f, {"collectives": {"all-gather": 1, "all-reduce": 1},
            "allow_f64": True, "allow_callbacks": True})] == []


def test_x003_uses_lowered_count_when_available():
    f = xl.parse_program_text(_HLO)
    f.lowered_concats = 0  # backend-introduced concat only
    assert [d.code for d in xl.run_rules(
        f, {"concatenates": 0, "allow_f64": True,
            "allow_callbacks": True})] == []
    f.lowered_concats = None
    assert [d.code for d in xl.run_rules(
        f, {"concatenates": 0, "allow_f64": True,
            "allow_callbacks": True})] == ["X003"]


def test_x005_x006_budget_overrides():
    f = xl.parse_program_text(_HLO)
    codes = [d.code for d in xl.run_rules(f)]
    assert codes == ["X005", "X006"]
    assert [d.code for d in xl.run_rules(
        f, {"allow_f64": True, "allow_callbacks": True})] == []


# ---------------------------------------------------------------------------
# seeded regressions from REAL executables
# ---------------------------------------------------------------------------

def test_x004_dropped_donation_flagged_and_clean_twin():
    """Donating an argument whose shape can never alias the output is
    the silent-2x-memory bug X004 exists for."""
    x, y = jnp.ones((8, 4)), jnp.ones((8, 4))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's own lower-time warning
        bad = jax.jit(lambda a, b: jnp.concatenate([a, b]),
                      donate_argnums=(0,)).lower(x, y).compile()
    diags = xl.lint_compiled(bad, name="bad", donated_params=[0],
                             budget={"concatenates": None})
    assert [d.code for d in diags] == ["X004"]
    good = jax.jit(lambda a, b: a + b,
                   donate_argnums=(0,)).lower(x, y).compile()
    assert xl.lint_compiled(good, name="good", donated_params=[0]) == []


def test_x005_injected_f64_flagged():
    with jax.enable_x64(True):
        comp = jax.jit(lambda a: a.astype(jnp.float64) * 2.0).lower(
            jnp.ones((4,), jnp.float32)).compile()
    assert "X005" in [d.code for d in xl.lint_compiled(comp, name="f64")]
    clean = jax.jit(lambda a: a * 2.0).lower(
        jnp.ones((4,), jnp.float32)).compile()
    assert xl.lint_compiled(clean, name="f32") == []


def test_x006_host_callback_flagged():
    def f(a):
        return jax.pure_callback(
            lambda v: onp.asarray(v),
            jax.ShapeDtypeStruct((4,), jnp.float32), a)

    comp = jax.jit(f).lower(jnp.ones((4,), jnp.float32)).compile()
    assert [d.code for d in xl.lint_compiled(comp, name="cb")] == ["X006"]
    assert xl.lint_compiled(comp, name="cb",
                            budget={"allow_callbacks": True}) == []


def test_x007_real_executable_forced_sync_and_clean_twin():
    """SEEDED: a shard_map gather in plain ``lax.all_gather`` form
    compiles to a blocking all-gather on this backend and must fail an
    ``async_required`` budget; ``ring_all_gather`` — the decomposed
    permute-ring form the overlap path emits — contains no all-gather
    op at all and is the clean twin (same math, lint-acceptable)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import collectives as coll

    mesh = make_mesh({"dp": 8})
    x = jnp.arange(32, dtype=jnp.float32).reshape((8, 4))
    budget = {"async_required": ["all-gather"], "allow_f64": True,
              "allow_callbacks": True}
    bad = jax.jit(shard_map(
        lambda a: jax.lax.all_gather(a, "dp", axis=0, tiled=True),
        mesh=mesh, in_specs=P("dp"), out_specs=P(),
        check_vma=False)).lower(x).compile()
    diags = xl.lint_compiled(bad, name="sync-gather", budget=budget)
    assert [d.code for d in diags] == ["X007"], diags
    assert "all-gather" in diags[0].message

    good_fn = jax.jit(shard_map(
        lambda a: coll.ring_all_gather(a, "dp", axis=0),
        mesh=mesh, in_specs=P("dp"), out_specs=P(), check_vma=False))
    good = good_fn.lower(x).compile()
    assert xl.lint_compiled(good, name="ring-gather", budget=budget) == []
    # the clean twin is the SAME gather, not a different computation
    onp.testing.assert_array_equal(onp.asarray(good_fn(x)), onp.asarray(x))


def test_x003_forced_extra_concatenate_via_arena_rule():
    """The arena invariant as a seeded regression: a step-shaped program
    that packs one concatenate too many must be flagged by the SAME
    check_arena_program call the kernels test/smoke use."""
    def packs_params(w1, w2, w3, g1, g2, g3, m1, m2, m3):
        grads = jnp.concatenate([g1.ravel(), g2.ravel(), g3.ravel()])
        params = jnp.concatenate([w1.ravel(), w2.ravel(), w3.ravel()])
        mom = jnp.concatenate([m1.ravel(), m2.ravel(), m3.ravel()])
        new_mom = 0.9 * mom + grads
        return params - 0.1 * new_mom, new_mom

    args = [jnp.ones((4, 2))] * 9
    txt = jax.jit(packs_params).lower(*args).as_text()
    diags = xl.check_arena_program(txt, name="packs-params")
    assert [d.code for d in diags] == ["X003"]
    assert "2" in diags[0].message
    # clean twin: within the pack + AD dual budget
    ok = jax.jit(lambda a, b: jnp.concatenate([a, b])).lower(
        jnp.ones((4,)), jnp.ones((4,))).as_text()
    assert xl.check_arena_program(ok, name="one-concat") == []


# ---------------------------------------------------------------------------
# the three compile seams (hooks) + env modes
# ---------------------------------------------------------------------------

class _CallbackNet(nn.HybridBlock):
    def __init__(self):
        super().__init__()
        self.d = nn.Dense(4, in_units=8)

    def forward(self, x):
        h = self.d(x)
        peek = jax.pure_callback(lambda a: onp.asarray(a),
                                 jax.ShapeDtypeStruct((), jnp.float32),
                                 h._data.sum())
        return h * (1.0 + 0.0 * mx.nd.NDArray(peek))


def _callback_net():
    net = _CallbackNet()
    net.initialize(mx.init.Xavier())
    net(mx.np.zeros((2, 8)))  # eager shape-discovery call
    return net


def test_cached_op_hook_warns_and_counts(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    tel.reset()
    net = _callback_net()
    net.hybridize()
    net(mx.np.zeros((2, 8)))  # eager (first after hybridize)
    with pytest.warns(RuntimeWarning, match=r"X006"):
        net(mx.np.zeros((2, 8)))  # first jit trace -> hook
    snap = tel.snapshot()
    assert snap["analysis.xla_lint.X006"]["value"] >= 1
    assert snap["analysis.xla_lint_findings"]["value"] >= 1


def test_cached_op_hook_raise_mode(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "raise")
    net = _callback_net()
    net.hybridize()
    net(mx.np.zeros((2, 8)))
    with pytest.raises(MXNetError, match="X006"):
        net(mx.np.zeros((2, 8)))


def test_cached_op_hook_off_by_default():
    net = _callback_net()
    net.hybridize()
    net(mx.np.zeros((2, 8)))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        out = net(mx.np.zeros((2, 8)))  # no lint, no warning
    assert out.shape == (2, 4)


def test_warmup_hook_and_block_budget(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    net = _callback_net()
    net.hybridize()
    with xl.capture() as cap:
        assert net.warmup((mx.np.zeros((2, 8)),)) == 1
    assert [d.code for f, dg in cap for d in dg] == ["X006"]
    # a block-attached budget silences the intended callback
    net2 = _callback_net()
    net2.hybridize()
    net2._xla_lint_budget = {"allow_callbacks": True}
    with xl.capture() as cap2:
        net2.warmup((mx.np.zeros((2, 8)),))
    assert [d for f, dg in cap2 for d in dg] == []


def test_serve_register_hook_attributes_to_entry(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    from mxnet_tpu.serve.registry import Registry

    net = _callback_net()
    with xl.capture() as cap:
        Registry().register("cbmodel", net, bucketer={0: [2, 4]},
                            sample=onp.zeros((8,), "float32"))
    # full bucket grid linted (2 shapes), attributed to the serve entry
    assert len(cap) == 2
    for facts, diags in cap:
        assert facts.name == "hybridize:serve.cbmodel"
        assert [d.code for d in diags] == ["X006"]
        assert diags[0].symbol == "hybridize:serve.cbmodel"


# ---------------------------------------------------------------------------
# X008: the precision="int8" contract (require_int8_dots)
# ---------------------------------------------------------------------------

def test_x008_fires_on_f32_twin_and_clean_on_int8_dot():
    # SEEDED repro: an f32 executable linted under the int8 contract —
    # the model claims int8 but no integer-accumulated dot survived
    f32 = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((4, 8), "float32"),
        jnp.zeros((8, 5), "float32")).compile()
    facts = xl.parse_program_text(f32.as_text(), name="twin")
    assert facts.int8_dot_count == 0
    codes = [d.code for d in
             xl.run_rules(facts, {"require_int8_dots": True})]
    assert codes == ["X008"]
    # without the budget flag the same facts are clean (default off)
    assert xl.run_rules(facts, {}) == []

    # clean twin: a real int8 dot, in BOTH dialects (XLA:CPU widens the
    # s8 operands to s32 pre-dot, so the integer OUTPUT type is what
    # the parser must key on)
    def q(a, b):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)

    low = jax.jit(q).lower(jnp.zeros((4, 8), jnp.int8),
                           jnp.zeros((8, 5), jnp.int8))
    for text in (low.as_text(), low.compile().as_text()):
        f = xl.parse_program_text(text)
        assert f.int8_dot_count == 1
        assert xl.run_rules(f, {"require_int8_dots": True}) == []
        assert f.to_dict()["int8_dots"] == 1


def test_x008_silent_on_dotless_executable():
    # an auxiliary executable with no dot at all (slot write, cache
    # growth pad) must not fail the contract — only dot-carrying
    # executables can prove or break it
    nod = jax.jit(lambda x: x + 1).lower(
        jnp.zeros((4,), "float32")).compile()
    facts = xl.parse_program_text(nod.as_text())
    assert facts.count("dot", "convolution") == 0
    assert xl.run_rules(facts, {"require_int8_dots": True}) == []


def test_x008_registry_int8_entry_clean_and_forced_f32_twin(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    from mxnet_tpu.serve.registry import Registry

    # the real pipeline: precision="int8" runs quantize_net at
    # registration and merges require_int8_dots into the lint budget —
    # every warmed executable carries the int8 dots
    rs = onp.random.RandomState(0)
    calib = [rs.rand(4, 8).astype("float32")]
    with xl.capture() as cap:
        Registry().register("mlp_q", _mlp(), bucketer={0: [2]},
                            sample=onp.zeros((8,), "float32"),
                            precision="int8", calib_data=calib)
    assert cap
    for facts, diags in cap:
        assert facts.int8_dot_count >= 1
        assert diags == []
    # forced twin: the same int8 CLAIM (budget flag) with the PTQ
    # rewrite bypassed — the grid serves f32 math and X008 fires
    with xl.capture() as cap2:
        Registry().register("mlp_f32_claim", _mlp(seed=1),
                            bucketer={0: [2]},
                            sample=onp.zeros((8,), "float32"),
                            lint_budget={"require_int8_dots": True})
    codes = [d.code for _f, dg in cap2 for d in dg]
    assert "X008" in codes, codes


# ---------------------------------------------------------------------------
# trainer seam: X001 (forced replicated opt state under zero1)
# ---------------------------------------------------------------------------

def _zero1_trainer(seed=0):
    return ShardedTrainer(_big_mlp(seed), _ce,
                          mesh=make_mesh({"dp": 8}), optimizer="sgd",
                          learning_rate=0.05, momentum=0.9,
                          partition="zero1")


def _force_replicated_opt_state(tr):
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(tr.mesh, P())
    tr.opt_state = [jax.device_put(jnp.asarray(s), repl)
                    for s in tr.opt_state]


def test_trainer_zero1_clean_then_forced_replicated(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    with xl.capture() as cap:
        assert _zero1_trainer().compile(_batch()) == 1
    assert [d.code for f, dg in cap for d in dg] == []
    # SEEDED: the state arrives replicated; the executable keeps it
    # replicated on the input side -> every device pays full state
    tr2 = _zero1_trainer(seed=1)
    _force_replicated_opt_state(tr2)
    with xl.capture() as cap2:
        assert tr2.compile(_batch()) == 1
    codes = [d.code for f, dg in cap2 for d in dg]
    assert "X001" in codes, codes
    # the finding names the oversized leaf, not a min-size-skipped one
    x001 = [d for f, dg in cap2 for d in dg if d.code == "X001"]
    assert any("weight" in d.message for d in x001)


def test_trainer_forced_replicated_raises_under_raise_mode(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "raise")
    tr = _zero1_trainer(seed=2)
    _force_replicated_opt_state(tr)
    with pytest.raises(MXNetError, match="X001"):
        tr.compile(_batch())


def test_trainer_zero1_collective_budget_x002(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    tr = _zero1_trainer(seed=3)
    tr._xla_lint_budget = {"collectives": {}}  # everything is a surprise
    with xl.capture() as cap:
        tr.compile(_batch())
    codes = [d.code for f, dg in cap for d in dg]
    assert "X002" in codes, codes
    # re-budgeting to the measured mix is clean (the --update-budgets
    # flow tools/xlalint.py automates)
    measured = {}
    for f, _dg in cap:
        for op, n in f.collective_counts.items():
            measured[op] = max(measured.get(op, 0), n)
    tr2 = _zero1_trainer(seed=3)
    tr2._xla_lint_budget = {"collectives": measured}
    with xl.capture() as cap2:
        tr2.compile(_batch())
    assert [d.code for f, dg in cap2 for d in dg] == []


def test_trainer_hook_collects_cost_and_sharding_facts(monkeypatch):
    monkeypatch.setenv("MXNET_XLA_LINT", "1")
    with xl.capture() as cap:
        _zero1_trainer(seed=4).compile(_batch())
    (facts, _diags), = cap
    assert facts.name == "trainer.step:HybridSequential"
    assert facts.collective_counts  # SPMD step has collectives
    assert facts.cost is None or facts.cost["flops"] > 0
    d = facts.to_dict()
    assert d["concatenates"] == facts.concat_count


# ---------------------------------------------------------------------------
# CLI pieces (no model builds: manifest plumbing only)
# ---------------------------------------------------------------------------

def test_mxlint_cli_knows_x_rules():
    import subprocess
    import sys
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "mxlint.py"),
         "--explain", "X003"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "concatenate-over-budget" in out.stdout


def test_budget_manifest_covers_canonical_models():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "tools", "xlalint_budgets.json")) as f:
        manifest = json.load(f)
    models = manifest["models"]
    for name in ("lenet_train_arena", "lenet_train_zero1", "resnet_infer",
                 "resnet_fused_bn_relu_infer", "bert_tiny_train",
                 "serve_mlp"):
        assert name in models, name
        b = models[name]
        assert set(b) == {"concatenates", "collectives", "allow_f64",
                          "allow_callbacks"}
        assert b["allow_f64"] is False and b["allow_callbacks"] is False
    # the arena model's checked-in budget IS the invariant
    assert models["lenet_train_arena"]["concatenates"] <= \
        xl.ARENA_CONCAT_BUDGET
    # the overlap model additionally carries the X007 contract: its
    # weight update may never fall back to blocking RS/AG
    ovl = models["lenet_train_zero1_overlap"]
    assert set(ovl["async_required"]) == {"reduce-scatter", "all-gather"}
    assert "all-gather" not in ovl["collectives"]
    assert "reduce-scatter" not in ovl["collectives"]
    # the bf16 AMP twin of the overlap model carries the SAME X007
    # contract — the dtype-policy transform must not cost the overlap
    bf16 = models["lenet_train_zero1_overlap_bf16"]
    assert set(bf16["async_required"]) == {"reduce-scatter", "all-gather"}
    assert "all-gather" not in bf16["collectives"]
    assert "reduce-scatter" not in bf16["collectives"]
    # the quantized serve entry carries the X008 contract: its grid may
    # never silently fall back to f32 math under the int8 claim
    assert models["serve_mlp_int8"]["require_int8_dots"] is True
