#!/usr/bin/env python
"""Flakiness checker: run a test many times under different seeds.

Analog of the reference's ``tools/flakiness_checker.py`` (SURVEY.md §4:
the reproducibility fixtures log ``MXNET_TEST_SEED=N`` per test; this
tool drives that hook in a loop to hunt seed-dependent failures).

Usage:
  python tools/flakiness_checker.py tests/test_foo.py::test_bar [-n 30]
  python tools/flakiness_checker.py test_foo.test_bar -n 100 --seed 7

Accepts pytest node ids or the reference's ``module.test_name`` spelling.
Each trial runs in its own pytest subprocess with MXNET_TEST_SEED set
(sequential seeds from --seed, or random ones with --random-seeds), the
CPU platform the suite runs on.  Exit 0 iff every trial passed; failures print
the exact MXNET_TEST_SEED to reproduce.

``--format=json`` emits findings in the mx.analysis diagnostic shape
(rule F001, same JSON stream tools/mxlint.py produces) so CI consumes
lint + flakiness results uniformly; trial progress moves to stderr.
"""
from __future__ import annotations

import argparse
import os
import random
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from mxlint import load_analysis  # noqa: E402 — stdlib-only loader


def to_nodeid(spec: str) -> str:
    """'test_module.test_name' -> 'tests/test_module.py::test_name';
    pytest node ids pass through."""
    if "::" in spec or spec.endswith(".py") or os.path.exists(spec):
        return spec
    if "." in spec:
        mod, _, name = spec.rpartition(".")
        cand = os.path.join("tests", mod.replace(".", os.sep) + ".py")
        if os.path.exists(os.path.join(ROOT, cand)):
            return f"{cand}::{name}"
    return spec


def main():
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("test", help="pytest node id or module.test_name")
    p.add_argument("-n", "--trials", type=int, default=30)
    p.add_argument("--seed", type=int, default=0,
                   help="first seed (sequential from here)")
    p.add_argument("--random-seeds", action="store_true",
                   help="draw seeds at random instead of sequentially")
    p.add_argument("-v", "--verbose", action="store_true",
                   help="stream pytest output for failing trials")
    p.add_argument("--format", choices=["text", "json"], default="text",
                   help="json: mx.analysis diagnostic stream (F001)")
    args = p.parse_args()

    say = print if args.format == "text" else \
        (lambda *a, **k: print(*a, file=sys.stderr,
                               **{k_: v for k_, v in k.items()
                                  if k_ != "file"}))
    nodeid = to_nodeid(args.test)
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")

    rng = random.Random(args.seed)
    failures = []
    for i in range(args.trials):
        seed = rng.randrange(2 ** 31) if args.random_seeds \
            else args.seed + i
        env["MXNET_TEST_SEED"] = str(seed)
        r = subprocess.run(
            [sys.executable, "-m", "pytest", nodeid, "-q", "-x",
             "--no-header", "-p", "no:cacheprovider"],
            cwd=ROOT, env=env, capture_output=True, text=True)
        if r.returncode in (2, 3, 4, 5):
            # collection/import error, internal error, usage error, or
            # nothing collected — seed-independent; reporting these as
            # "flaky" would mask that the test never ran
            say(f"error: pytest could not run {nodeid!r} "
                f"(rc={r.returncode}):")
            say((r.stdout + r.stderr)[-1500:])
            if args.format == "json":
                # consumers of the stream still get a well-formed doc
                # (X000 = tool could not analyze, docs/analysis.md)
                ana = load_analysis()
                sys.stdout.write(ana.diagnostics.dumps_json(
                    [ana.Diagnostic(
                        path=nodeid.split("::", 1)[0], line=0,
                        code="X000",
                        message=(f"pytest could not run {nodeid!r} "
                                 f"(rc={r.returncode}): "
                                 + (r.stdout + r.stderr)[-800:]),
                        symbol=nodeid, source="flakiness-checker")],
                    tool="flakiness_checker", trials=args.trials,
                    failed=0))
            return 2
        ok = r.returncode == 0
        say(f"trial {i + 1}/{args.trials} seed={seed}: "
            f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(seed)
            if args.verbose:
                say(r.stdout[-3000:])
                say(r.stderr[-1000:])
    if args.format == "json":
        ana = load_analysis()
        path = nodeid.split("::", 1)[0]
        diags = [ana.Diagnostic(
            path=path, line=0, code="F001",
            message=(f"failed under MXNET_TEST_SEED={s} "
                     f"({len(failures)}/{args.trials} trials failed); "
                     f"reproduce: MXNET_TEST_SEED={s} python -m pytest "
                     f"{nodeid}"),
            symbol=nodeid, source="flakiness-checker")
            for s in failures]
        sys.stdout.write(ana.diagnostics.dumps_json(
            diags, tool="flakiness_checker", trials=args.trials,
            failed=len(failures)))
        return 1 if failures else 0
    if failures:
        print(f"\nFLAKY: {len(failures)}/{args.trials} trials failed; "
              "reproduce with:")
        for s in failures[:10]:
            print(f"  MXNET_TEST_SEED={s} python -m pytest {nodeid}")
        return 1
    print(f"\nstable: {args.trials}/{args.trials} trials passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
