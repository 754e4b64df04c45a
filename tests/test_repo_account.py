"""The repo gives one account of itself: its documents name only files
that are in the tree, and `benchmarks/` is the only thing that measures
it (PERF.md; BENCHMARK.json)."""
import fnmatch
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what building, testing and running leave behind (.gitignore)
LEFT_BEHIND = {".git", "__pycache__", ".pytest_cache", ".jax_cache",
               ".bench_out", "chiprun_out", "build"}

DOCUMENTS = (["README.md", "tools/README.md",
              ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT) for p in
                      glob.glob(os.path.join(ROOT, "docs", "*.md"))))

# the only names a document may give that are no file of this repo
UPSTREAM_PREFIX = "python/mxnet/"      # the reference's own paths (API.md)
PLACEHOLDERS = {"your_script.py", "train.py"}

PY_PATH = re.compile(r"[A-Za-z0-9_.*/-]*[A-Za-z0-9_*]\.py\b")


@pytest.fixture(scope="module")
def tree():
    """Every file of the checkout, as a path from its root."""
    files = []
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in LEFT_BEHIND]
        rel = os.path.relpath(base, ROOT)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def _resolves(name, tree):
    """`name` is the end of some file's path, whole components only; a
    `*` in it stands for any run of characters."""
    name = name.lstrip("./")
    return any(fnmatch.fnmatchcase("/" + f, "*/" + name) for f in tree)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_that_exist(doc, tree):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    named = {m.group(0) for m in PY_PATH.finditer(text)}
    named = {n for n in named if n not in PLACEHOLDERS
             and not n.startswith(UPSTREAM_PREFIX)}
    assert named, f"{doc} names no script at all: is the pattern broken?"
    missing = sorted(n for n in named if not _resolves(n, tree))
    assert not missing, f"{doc} names files that are not in the tree: " \
                        f"{missing}"


def test_one_yardstick(tree):
    """No benchmark script beside `benchmarks/`, and nothing outside it
    that an environment switch of the old scripts' family could steer."""
    top = [f for f in tree if "/" not in f
           and fnmatch.fnmatchcase(f, "bench*.py")]
    assert top == [], top
    reads_switch = re.compile(r"(environ|getenv)[^\n]*[\"']BENCH_\w+")
    readers = []
    for f in tree:
        if (not f.endswith(".py")
                or f.startswith(("benchmarks/", "tests/benchmarks/"))):
            continue
        with open(os.path.join(ROOT, f), errors="replace") as fh:
            if reads_switch.search(fh.read()):
                readers.append(f)
    assert readers == [], readers
