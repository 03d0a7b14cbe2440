#!/usr/bin/env python
"""mxlint — hybridize-safety linter CLI over mx.analysis.

Static staging-hazard analysis for this framework (rule catalog:
docs/analysis.md, ``--rules`` to list, ``--explain CODE`` for one).
Machine-readable by default in CI via ``--format=json``; the committed
baseline makes legacy violations explicit while new ones fail the gate.

Usage:
  python tools/mxlint.py mxnet_tpu/ example/
  python tools/mxlint.py --format=json --baseline tools/mxlint_baseline.json <paths>
  python tools/mxlint.py --write-baseline --baseline tools/mxlint_baseline.json <paths>
  python tools/mxlint.py --explain H003
  python tools/mxlint.py --rules

Exit codes: 0 clean (or fully baselined), 1 new violations, 2 usage.

The analysis package is loaded standalone (no framework / jax import),
so a full-tree lint is sub-second — cheap enough for a pre-commit hook.
All CLI plumbing (baselines, output formats, catalog access) is shared
with tools/threadlint.py via mx.analysis.lint_cli.
"""
from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_analysis():
    """Load mxnet_tpu.analysis WITHOUT executing mxnet_tpu/__init__.py
    (which imports jax).  The package is stdlib-only by contract."""
    name = "_mxlint_analysis"
    if name in sys.modules:
        return sys.modules[name]
    pkg_dir = os.path.join(ROOT, "mxnet_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ana = load_analysis()
    # the concurrency family (T) belongs to tools/threadlint.py; the
    # two tools partition the catalog
    return ana.lint_cli.run(argv, tool="mxlint",
                            lint_paths_fn=ana.lint_paths, root=ROOT,
                            rule_prefixes=("H", "L", "E", "X"),
                            description=__doc__)


if __name__ == "__main__":
    sys.exit(main())
