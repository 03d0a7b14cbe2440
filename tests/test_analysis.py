"""mx.analysis: hybridize-safety linter + engine dependency checker +
retrace guard (ISSUE 2).

Static rules are proven the strong way: every rule code must catch a
minimal repro AND pass a clean twin that does the same job the staged-
safe way — the linter is only useful if the fix it recommends lints
clean.  The runtime checker must detect a seeded undeclared-dependency
push and stay silent on correctly declared concurrent work, under BOTH
engines (the NaiveEngine error-contract alignment is asserted in
test_exc_and_threads.py).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import engine_check as echk
from mxnet_tpu.analysis import retrace
from mxnet_tpu.analysis.diagnostics import RULES
from mxnet_tpu.analysis.hybrid_lint import lint_source

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# static linter: every rule catches a minimal repro AND passes a clean twin
# ---------------------------------------------------------------------------

def _forward(body: str) -> str:
    return textwrap.dedent("""\
        import numpy as np
        from mxnet_tpu.gluon import HybridBlock

        class Net(HybridBlock):
            def forward(self, x):
        {body}
                return x
        """).format(body=textwrap.indent(textwrap.dedent(body), " " * 8))


_RULE_CASES = [
    ("H001",
     _forward("h = x.asnumpy()"),
     _forward("h = x + 1")),
    ("H002",
     _forward("s = float(x.sum())"),
     _forward("s = x.sum()")),
    ("H003",
     _forward("if x.sum() > 0:\n    x = x * 2"),
     # static-metadata branch is trace-stable: must lint clean
     _forward("if x.ndim == 2:\n    x = x * 2")),
    ("H004",
     _forward("assert x.mean() < 5"),
     _forward("assert x.shape[0] > 0")),
    ("H005",
     _forward("x = x[x > 0]"),
     _forward("x = x * (x > 0)")),
    ("H006",
     _forward("noise = np.random.rand(3)\nx = x + noise"),
     _forward("x = x + 0.5")),
    ("H007",
     _forward("x[0] = 0.0"),
     _forward("x = x * 1.0")),
    ("H008",
     _forward("x = self.child(x, cfg=[1, 2])"),
     _forward("x = self.child(x)")),
    ("H009",
     _forward("h = x + 1").replace("def forward(self, x):",
                                   "def forward(self, x, opts=[1]):"),
     _forward("h = x + 1").replace("def forward(self, x):",
                                   "def forward(self, x, opts=None):")),
    ("H010",
     _forward("print(x)"),
     _forward("pass")),
    ("L101",
     textwrap.dedent("""\
        def train(trainer, batches):
            for x, y in batches:
                loss = trainer.step(x, y)
                print(loss.asnumpy())
        """),
     textwrap.dedent("""\
        def train(trainer, batches):
            losses = []
            for x, y in batches:
                losses.append(trainer.step(x, y))
            print(sum(losses))
        """)),
    ("L102",
     textwrap.dedent("""\
        def train(trainer, batches):
            for x, y in batches:
                loss = trainer.step(x, y)
                log(float(loss))
        """),
     # the non-blocking idiom: the lazy loss rides async dispatch and is
     # read ONCE, after the loop
     textwrap.dedent("""\
        def train(trainer, batches):
            for x, y in batches:
                loss = trainer.step(x, y)
            return float(loss)
        """)),
]


@pytest.mark.parametrize("code,bad,good", _RULE_CASES,
                         ids=[c[0] for c in _RULE_CASES])
def test_rule_catches_repro_and_passes_clean_twin(code, bad, good):
    bad_codes = [d.code for d in lint_source(bad, "bad.py")]
    assert code in bad_codes, f"{code} missed its repro: {bad_codes}"
    good_diags = lint_source(good, "good.py")
    assert not good_diags, f"clean twin flagged: {good_diags}"


def test_rule_codes_all_documented():
    for code, _, _ in _RULE_CASES:
        assert code in RULES
    for code in ("E001", "E002", "E003", "J001", "F001"):
        assert code in RULES  # runtime + flakiness rules share the catalog


def test_l102_ignores_non_trainer_step_results():
    """RL-style loops call env.step() and .backward() in the same loop;
    host-side reads of env.step results must not be reported as loss
    syncs (the capture is restricted to trainer-like receivers)."""
    src = textwrap.dedent("""\
        def train(agent, env):
            for ep in range(10):
                obs, reward, done, info = env.step(agent.act())
                log(float(reward))
                agent.objective.backward()
        """)
    assert not lint_source(src, "rl.py")
    mixed = textwrap.dedent("""\
        def train(trainer, env, batches):
            for x, y in batches:
                obs = env.step(x)
                loss = trainer.step(x, y)
                log(float(loss), float(obs))
        """)
    assert [d.code for d in lint_source(mixed, "m.py")] == ["L102"]


def test_is_none_branches_are_trace_stable():
    """`x is None` specializes via the argument tree — loss.py/rnn_layer
    style optional-argument branching must NOT fire H003."""
    src = _forward("if x is not None:\n    x = x * 2\n"
                   "y = (x, 1) if x is None else (x, 2)")
    assert not lint_source(src, "t.py")


def test_inline_suppression_and_file_suppression():
    src = _forward("h = x.asnumpy()  # mxlint: disable=H001")
    assert not lint_source(src, "t.py")
    src = _forward("h = x.asnumpy()  # mxlint: disable=all")
    assert not lint_source(src, "t.py")
    src = ("# mxlint: disable-file=H001\n"
           + _forward("h = x.asnumpy()"))
    assert not lint_source(src, "t.py")
    # the wrong code does NOT silence
    src = _forward("h = x.asnumpy()  # mxlint: disable=H003")
    assert [d.code for d in lint_source(src, "t.py")] == ["H001"]


def test_taint_propagates_through_assignment_chains():
    src = _forward("a = x * 2\nb = a.sum()\nif b > 0:\n    x = x + 1")
    assert "H003" in [d.code for d in lint_source(src, "t.py")]


def test_hybrid_subclass_resolved_transitively():
    src = textwrap.dedent("""\
        from mxnet_tpu.gluon import HybridBlock

        class Base(HybridBlock):
            pass

        class Child(Base):
            def forward(self, x):
                return x.asnumpy()

        class NotABlock:
            def forward(self, x):
                return x.asnumpy()   # plain class: not linted
        """)
    diags = lint_source(src, "t.py")
    assert [d.symbol for d in diags] == ["Child.forward"]


# ---------------------------------------------------------------------------
# mxlint CLI: json shape, exit codes, baseline flow
# ---------------------------------------------------------------------------

def _run_mxlint(args, cwd=ROOT):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py")] + args,
        cwd=cwd, capture_output=True, text=True, timeout=120)


def test_mxlint_cli_json_exit_codes_and_baseline(tmp_path):
    bad = tmp_path / "badmod.py"
    bad.write_text(_forward("h = x.asnumpy()"))
    r = _run_mxlint(["--format=json", str(bad)])
    assert r.returncode == 1, r.stderr
    doc = json.loads(r.stdout)
    assert doc["version"] == 1 and doc["tool"] == "mxlint"
    (d,) = doc["diagnostics"]
    assert d["code"] == "H001" and d["symbol"] == "Net.forward"
    assert d["line"] > 0 and d["path"].endswith("badmod.py")
    # baseline the violation -> gate goes green, violation listed as known
    base = tmp_path / "baseline.json"
    r = _run_mxlint(["--write-baseline", "--baseline", str(base), str(bad)])
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_mxlint(["--format=json", "--baseline", str(base), str(bad)])
    assert r.returncode == 0
    doc = json.loads(r.stdout)
    assert doc["diagnostics"] == [] and len(doc["baselined"]) == 1
    # a NEW violation still fails against the old baseline
    bad.write_text(_forward("h = x.asnumpy()\ng = x.item()"))
    r = _run_mxlint(["--format=json", "--baseline", str(base), str(bad)])
    assert r.returncode == 1


def test_mxlint_tree_is_clean():
    """Acceptance: the in-tree sources lint clean (true positives fixed,
    intentional syncs carry explicit suppressions)."""
    r = _run_mxlint(["--baseline", "tools/mxlint_baseline.json",
                     "mxnet_tpu", "example"])
    assert r.returncode == 0, r.stdout


def test_flakiness_checker_emits_same_json_shape(tmp_path):
    t = tmp_path / "test_tiny_probe.py"
    t.write_text("import os\n"
                 "def test_seed_parity():\n"
                 "    assert int(os.environ['MXNET_TEST_SEED']) % 2 == 0\n")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "flakiness_checker.py"),
         str(t) + "::test_seed_parity", "-n", "2", "--seed", "0",
         "--format=json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr  # seed 1 fails
    doc = json.loads(r.stdout)
    assert doc["version"] == 1 and doc["tool"] == "flakiness_checker"
    (d,) = doc["diagnostics"]
    assert d["code"] == "F001" and "MXNET_TEST_SEED=1" in d["message"]
    assert doc["trials"] == 2 and doc["failed"] == 1
    # a test pytest cannot even run still yields a well-formed document
    # (X000 analysis-error), not an empty stdout
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "flakiness_checker.py"),
         str(tmp_path / "no_such_test.py") + "::nope", "-n", "1",
         "--format=json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode == 2
    doc = json.loads(r.stdout)
    assert doc["diagnostics"][0]["code"] == "X000"


# ---------------------------------------------------------------------------
# runtime engine dependency checker
# ---------------------------------------------------------------------------

@pytest.fixture()
def checked_engine():
    eng = echk.install()
    echk.clear()
    try:
        yield eng
    finally:
        echk.uninstall()


def test_engine_check_detects_underdeclared_push(checked_engine):
    """Acceptance: a deliberately under-declared push is detected."""
    eng = checked_engine
    owner = eng.new_var()
    arr = mx.nd.zeros((4,))
    echk.bind(arr, owner)
    done = eng.new_var()
    eng.push(lambda: arr.asnumpy(), write=[done], name="rogue_reader")
    eng.wait_for_var(done)
    codes = [d.code for d in echk.diagnostics()]
    assert codes == ["E001"], codes
    d = echk.diagnostics()[0]
    assert d.symbol == "rogue_reader" and d.source == "engine-check"
    for v in (owner, done):
        eng.delete_var(v)


def test_engine_check_detects_underdeclared_write(checked_engine):
    eng = checked_engine
    owner = eng.new_var()
    arr = mx.nd.zeros((2,))
    echk.bind(arr, owner)
    done = eng.new_var()
    eng.push(lambda: arr._set_data(mx.nd.ones((2,))._data),
             write=[done], name="rogue_writer")
    eng.wait_for_var(done)
    assert "E002" in [d.code for d in echk.diagnostics()]
    for v in (owner, done):
        eng.delete_var(v)


def test_engine_check_declared_read_read_no_false_positive(checked_engine):
    """Acceptance: correctly-declared concurrent read/read stays silent."""
    eng = checked_engine
    owner = eng.new_var()
    arr = mx.nd.array(onp.arange(8, dtype="f4"))
    echk.bind(arr, owner)
    outs, vars_ = [], []
    for i in range(4):
        v = eng.new_var()
        vars_.append(v)
        eng.push(lambda: outs.append(float(arr.asnumpy().sum())),
                 read=[owner], write=[v], name=f"reader{i}")
    eng.wait_for_all()
    assert outs == [28.0] * 4
    assert echk.diagnostics() == []
    for v in [owner] + vars_:
        eng.delete_var(v)


def test_engine_check_ops_through_dispatch_are_seen(checked_engine):
    """Reads via op dispatch (not just .asnumpy) hit the checker."""
    eng = checked_engine
    owner = eng.new_var()
    arr = mx.nd.ones((3,))
    echk.bind(arr, owner)
    done = eng.new_var()
    eng.push(lambda: (arr + 1).wait_to_read(), write=[done],
             name="dispatch_reader")
    eng.wait_for_var(done)
    assert "E001" in [d.code for d in echk.diagnostics()]
    for v in (owner, done):
        eng.delete_var(v)


def test_engine_check_auto_binds_written_arrays(checked_engine):
    """A write inside a single-write-var push establishes ownership; a
    later push touching the array without that var is flagged."""
    eng = checked_engine
    produced = eng.new_var()
    target = mx.nd.zeros((2,))
    eng.push(lambda: target._set_data(mx.nd.ones((2,))._data),
             write=[produced], name="producer")
    eng.wait_for_var(produced)
    assert echk.diagnostics() == []   # producer declared its write
    rogue = eng.new_var()
    eng.push(lambda: target.asnumpy(), write=[rogue], name="consumer")
    eng.wait_for_var(rogue)
    assert "E001" in [d.code for d in echk.diagnostics()]
    ok = eng.new_var()
    echk.clear()
    eng.push(lambda: target.asnumpy(), read=[produced], write=[ok],
             name="good_consumer")
    eng.wait_for_var(ok)
    assert echk.diagnostics() == []
    for v in (produced, rogue, ok):
        eng.delete_var(v)


def test_engine_check_wait_inside_push(checked_engine):
    """E003: wait_for_all inside a push is a guaranteed self-deadlock on
    the threaded engine — the checker records it and neuters the wait
    instead of hanging."""
    eng = checked_engine
    v = eng.new_var()
    eng.push(lambda: eng.wait_for_all(), write=[v], name="bad_waiter")
    eng.wait_for_var(v)
    diags = echk.diagnostics()
    assert [d.code for d in diags] == ["E003"]
    assert diags[0].symbol == "bad_waiter"
    eng.delete_var(v)


def test_engine_check_raise_mode(checked_engine):
    eng = echk.install(raise_on_violation=True)
    try:
        owner = eng.new_var()
        arr = mx.nd.zeros((2,))
        echk.bind(arr, owner)
        boom = eng.new_var()
        eng.push(lambda: arr.asnumpy(), write=[boom], name="rogue")
        with pytest.raises(mx.MXNetError, match="E001"):
            eng.wait_for_var(boom)
        for v in (owner, boom):
            eng.delete_var(v)
    finally:
        echk.install(raise_on_violation=False)


def test_engine_check_identical_under_naive_engine():
    """The checker reports the same codes when wrapping NaiveEngine —
    push contexts are set during inline execution too."""
    from mxnet_tpu import engine as eng_mod

    naive = echk.CheckingEngine(eng_mod.NaiveEngine())
    prev_diags = len(echk.diagnostics())
    echk._ACTIVE = True
    try:
        owner = naive.new_var()
        arr = mx.nd.zeros((2,))
        echk.bind(arr, owner)
        done = naive.new_var()
        naive.push(lambda: arr.asnumpy(), write=[done], name="rogue")
        naive.wait_for_var(done)
        v2 = naive.new_var()
        naive.push(lambda: naive.wait_for_all(), write=[v2], name="waiter")
        naive.wait_for_var(v2)
        codes = [d.code for d in echk.diagnostics()[prev_diags:]]
        assert codes == ["E001", "E003"], codes
    finally:
        echk._ACTIVE = False
        echk.clear()


def test_engine_check_env_var_installs(tmp_path):
    """MXNET_ENGINE_CHECK=1 wraps the global engine at creation."""
    code = textwrap.dedent("""\
        import mxnet_tpu as mx
        from mxnet_tpu import engine
        from mxnet_tpu.analysis import engine_check as echk
        eng = engine.get()
        assert type(eng).__name__ == "CheckingEngine", type(eng)
        assert echk.enabled()
        owner = eng.new_var()
        arr = mx.nd.zeros((2,))
        echk.bind(arr, owner)
        done = eng.new_var()
        eng.push(lambda: arr.asnumpy(), write=[done], name="rogue")
        eng.wait_for_var(done)
        assert [d.code for d in echk.diagnostics()] == ["E001"]
        print("ENV-CHECK-OK")
        """)
    env = {**os.environ, "MXNET_ENGINE_CHECK": "1",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENV-CHECK-OK" in r.stdout


# ---------------------------------------------------------------------------
# retrace guard (J001 over the jit cache)
# ---------------------------------------------------------------------------

def test_retrace_guard_flags_signature_growth_and_culprit():
    retrace.reset()
    prev = retrace.set_limit(3)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        for n in (1, 2, 3, 4):   # first call warms up eagerly
            net(mx.nd.array(onp.ones((n, 8), "f4")))
        rep = retrace.report()
        assert len(rep) == 1 and rep[0].code == "J001"
        assert rep[0].symbol == "Dense"
        # points at the offending argument, not the parameters
        assert "argument leaf #0" in rep[0].message
        assert "state/param" not in rep[0].message
    finally:
        retrace.set_limit(prev)
        retrace.reset()


def test_retrace_guard_silent_under_limit():
    retrace.reset()
    prev = retrace.set_limit(50)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        for n in (1, 2, 3):
            net(mx.nd.array(onp.ones((n, 8), "f4")))
        assert retrace.report() == []
    finally:
        retrace.set_limit(prev)
        retrace.reset()


def test_retrace_telemetry_counter_ticks():
    from mxnet_tpu import telemetry as tel

    retrace.reset()
    prev_lim = retrace.set_limit(2)
    prev_en = tel.set_enabled(True)
    tel.reset()
    try:
        net = mx.gluon.nn.Dense(2)
        net.initialize()
        net.hybridize()
        for n in (1, 2, 3):
            net(mx.nd.array(onp.ones((n, 4), "f4")))
        snap = tel.snapshot()
        assert snap.get("hybridize.retrace_warnings", {}).get("value") == 1
    finally:
        tel.reset()
        tel.set_enabled(prev_en)
        retrace.set_limit(prev_lim)
        retrace.reset()


# ---------------------------------------------------------------------------
# shape-churn storm (J002): repro + clean twins
# ---------------------------------------------------------------------------

def test_shape_churn_storm_repro():
    """Sustained churn — a new signature every call past the
    MIN*EVERY floor — with no bucketer: J002 fires once, names the
    churning argument slot, and ticks its counter."""
    from mxnet_tpu import telemetry as tel

    retrace.reset()
    prev = retrace.set_churn_params(min_sigs=3, every=2)
    prev_lim = retrace.set_limit(50)   # keep J001 out of the way
    prev_en = tel.set_enabled(True)
    tel.reset()
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        for n in range(1, 10):   # first call warms up eagerly
            net(mx.nd.array(onp.ones((n, 8), "f4")))
        codes = [d.code for d in retrace.report()]
        assert codes == ["J002"]
        d = retrace.report()[0]
        assert d.symbol == "Dense"
        assert "argument leaf #0" in d.message
        assert "bucketer" in d.message
        snap = tel.snapshot()
        assert snap.get("hybridize.shape_churn_warnings",
                        {}).get("value") == 1
        # fires once per block type, not per trace
        net(mx.nd.array(onp.ones((20, 8), "f4")))
        assert [d.code for d in retrace.report()] == ["J002"]
    finally:
        tel.reset()
        tel.set_enabled(prev_en)
        retrace.set_limit(prev_lim)
        retrace.set_churn_params(*prev)
        retrace.reset()


def test_shape_churn_clean_twin_loader_bucketed_stream():
    """A bounded bucket set discovered in the first calls (what a
    DataLoader(bucket_spec=...) pipeline produces) then reused for many
    more: traces stop before the sustained-churn floor — no J002 even
    though the block itself has no bucketer attached."""
    retrace.reset()
    prev = retrace.set_churn_params(min_sigs=3, every=4)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        buckets = (8, 16, 32, 64)
        for _ in range(10):
            for b in buckets:     # all buckets appear in round 1
                net(mx.nd.array(onp.ones((b, 8), "f4")))
        assert retrace.report() == []
    finally:
        retrace.set_churn_params(*prev)
        retrace.reset()


def test_shape_churn_clean_twin_bucketed():
    """Same drifting shapes with a bucketer attached: the signature set
    is bounded by construction, so the guard stays silent."""
    retrace.reset()
    prev = retrace.set_churn_params(min_sigs=3, every=4)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize(bucketer={0: [4, 8]})
        for n in (1, 2, 3, 4, 5, 6):
            net(mx.nd.array(onp.ones((n, 8), "f4")))
        assert retrace.report() == []
        assert len(net._cached_op._traced) <= 2
    finally:
        retrace.set_churn_params(*prev)
        retrace.reset()


def test_shape_churn_clean_twin_stable_shapes():
    """A bounded shape set below MXNET_SHAPE_CHURN_MIN, reused over many
    calls: the distinct-signature count never reaches the threshold, so
    no amount of traffic fires J002 (the min exists exactly so small
    legitimate shape sets stay silent)."""
    retrace.reset()
    prev = retrace.set_churn_params(min_sigs=4, every=4)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net.hybridize()
        for _ in range(10):
            for n in (2, 4, 6):
                net(mx.nd.array(onp.ones((n, 8), "f4")))
        assert [d.code for d in retrace.report()] == []
    finally:
        retrace.set_churn_params(*prev)
        retrace.reset()


def test_shape_churn_warmup_traces_exempt():
    """warmup() sweeps compile many signatures deliberately (n_calls is
    unreported); the churn rate must not count them."""
    retrace.reset()
    prev = retrace.set_churn_params(min_sigs=2, every=4)
    try:
        net = mx.gluon.nn.Dense(4)
        net.initialize()
        net(mx.np.ones((1, 8)))
        net.hybridize()
        net.warmup([(2, 8), (3, 8), (4, 8), (5, 8)])
        assert retrace.report() == []
    finally:
        retrace.set_churn_params(*prev)
        retrace.reset()


def test_j002_in_rule_catalog():
    assert "J002" in RULES
    assert "shape-churn-storm" in mx.analysis.rule_doc("J002")
    assert "bucket" in mx.analysis.rule_doc("J002")


# ---------------------------------------------------------------------------
# package surface
# ---------------------------------------------------------------------------

def test_analysis_namespace_exported():
    assert mx.analysis is analysis
    assert callable(mx.analysis.lint_source)
    assert "H001" in mx.analysis.RULES
    assert "suppress" in mx.analysis.rule_doc("H003")
