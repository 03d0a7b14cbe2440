"""The documents and the option list are held to the tree.

Two records a deletion leaves stale without anything failing: a document
that still names a file that went, and an ``MXNET_*`` option that nothing
counts.  (a) every back-ticked token of ``README.md`` and ``docs/*.md``
that looks like a path of this repo names something that exists, or
something ``.gitignore`` says a run leaves behind; (b) the ``MXNET_*``
names the code mentions are exactly ``tests/mxnet_options.txt`` — a new
option has to be added there by hand, and a deletion shortens it.
"""
import fnmatch
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))

_TOKEN = re.compile(r"`([^`\s]+)`")
# a path under one of the tree's directories, or a bare top-level file
_PATH = re.compile(
    r"^(?:(?:tools|mxnet_tpu|chipbench|tests|example|docs|src)/[\w./*-]+"
    r"|[A-Za-z_][\w-]*\.(?:py|md|json|jsonl))$")


with open(os.path.join(ROOT, ".gitignore")) as _f:
    IGNORED = [ln.strip() for ln in _f
               if ln.strip() and not ln.startswith("#")]


def _left_behind(path):
    """Whether ``.gitignore`` lists ``path``: a file a run writes."""
    for p in IGNORED:
        if p.endswith("/"):
            if ("/" + p) in ("/" + path + "/"):     # a directory, anywhere
                return True
        elif fnmatch.fnmatch(path, p) or fnmatch.fnmatch(
                os.path.basename(path), p):
            return True
    return False


def _missing(doc):
    out = []
    with open(os.path.join(ROOT, doc)) as f:
        for n, line in enumerate(f, 1):
            for token in _TOKEN.findall(line):
                # `file.py:123`, `file.py::test_name`, `dir/file.py,`
                path = token.split(":")[0].rstrip(".,;")
                if not _PATH.match(path):
                    continue
                full = os.path.join(ROOT, path)
                if os.path.exists(full) or ("*" in path and glob.glob(full)):
                    continue
                if not _left_behind(path):
                    out.append(f"{doc}:{n}: `{token}`")
    return out


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    assert _missing(doc) == []


_OPTION = re.compile(r"MXNET_[A-Z0-9_]+")
_OPTION_ROOTS = ["mxnet_tpu", "tools", "chipbench", "chip_smoke.py",
                 "__graft_entry__.py"]


def _option_names():
    names = set()
    for root in _OPTION_ROOTS:
        full = os.path.join(ROOT, root)
        files = [full] if os.path.isfile(full) else [
            os.path.join(d, f) for d, _dirs, fs in os.walk(full)
            for f in fs if f.endswith(".py")]
        for path in files:
            with open(path, encoding="utf-8") as f:
                names.update(_OPTION.findall(f.read()))
    return names


def test_mxnet_options_are_the_checked_in_list():
    """No option appears unannounced (ROADMAP D6): adding one means
    adding it to ``tests/mxnet_options.txt``, deleting one shortens it."""
    with open(os.path.join(ROOT, "tests", "mxnet_options.txt")) as f:
        listed = f.read().split()
    assert listed == sorted(set(listed))
    found = _option_names()
    assert sorted(found - set(listed)) == [], "not in the list"
    assert sorted(set(listed) - found) == [], "listed, but gone"
