"""The documents name what is there.

Three checks that read files and import nothing of JAX:

* every path a document writes between backticks (or in a fenced block)
  that ends ``.py``, ``.md``, ``.json``, ``.jsonl``, ``.sh`` or ``/`` and
  starts at a top-level name of this checkout exists, ``file.py:123`` line
  suffixes stripped. The source tree's citations (``horovod/...``,
  ``docs/benchmarks.rst``) start elsewhere or end otherwise and are not
  this repo's to hold;
* every ``HOROVOD_*`` / ``HVD_*`` name in ``docs/knobs.md`` is named under
  ``horovod_tpu/``, ``bin/`` or in ``setup.py``;
* every such whole name under ``horovod_tpu/`` is in ``docs/knobs.md`` (a
  name that ends in ``_`` is a prefix some code builds names from).
"""

import fnmatch
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))
PATH_ENDINGS = (".py", ".md", ".json", ".jsonl", ".sh", "/")
CODE_SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)
LINE_SUFFIX = re.compile(r":[0-9][0-9,\-]*$")
KNOB = re.compile(r"\b(?:HOROVOD|HVD)_[A-Z0-9_]+\b")


@pytest.fixture(scope="module")
def top_level_names():
    """What sits at the root of the checkout and git would commit: no dot
    names, nothing ``.gitignore`` lists (build and run leftovers)."""
    with open(os.path.join(ROOT, ".gitignore")) as f:
        ignored = [line.strip().rstrip("/") for line in f
                   if line.strip() and not line.startswith("#")]
    return {n for n in os.listdir(ROOT)
            if not n.startswith(".")
            and not any(fnmatch.fnmatch(n, pat) for pat in ignored)}


def _named_paths(text, top):
    for span in CODE_SPAN.findall(text):
        for word in span.strip("`").split():
            path = LINE_SUFFIX.sub("", word)
            if path.endswith(PATH_ENDINGS) and path.split("/")[0] in top:
                yield path


def _knobs_named_in(paths):
    names = set()
    for path in paths:
        with open(path, errors="ignore") as f:
            names.update(KNOB.findall(f.read()))
    return names


def _files_under(*parts):
    return [p for p in glob.glob(os.path.join(ROOT, *parts, "**", "*"),
                                 recursive=True)
            if os.path.isfile(p) and "__pycache__" not in p
            and not p.endswith((".so", ".o", ".pyc"))]


@pytest.fixture(scope="module")
def documented_knobs():
    return _knobs_named_in([os.path.join(ROOT, "docs", "knobs.md")])


@pytest.fixture(scope="module")
def package_knobs():
    return _knobs_named_in(_files_under("horovod_tpu"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document, top_level_names):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    missing = sorted({p for p in _named_paths(text, top_level_names)
                      if not os.path.exists(os.path.join(ROOT, p))})
    assert not missing, f"{document} names paths that are not there: {missing}"


def test_every_knob_in_knobs_md_is_named_by_the_code(documented_knobs,
                                                     package_knobs):
    in_code = package_knobs | _knobs_named_in(
        _files_under("bin") + [os.path.join(ROOT, "setup.py")])
    assert documented_knobs, "docs/knobs.md lists no variable"
    stale = sorted(documented_knobs - in_code)
    assert not stale, f"docs/knobs.md lists variables no code names: {stale}"


def test_every_knob_the_package_names_is_in_knobs_md(documented_knobs,
                                                     package_knobs):
    unlisted = sorted(n for n in package_knobs - documented_knobs
                      if not n.endswith("_"))
    assert not unlisted, f"docs/knobs.md does not list: {unlisted}"
