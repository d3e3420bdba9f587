"""Every repository path a document names in backticks exists.

A document that teaches a file that is gone sends its reader to git's
history (PR 49 deleted 85 files that ~150 lines still cited). One case a
document; a token counts as a repository path when it

- starts with `tools/`, `benchmark/`, `paddle_tpu/`, `tests/` or `docs/`;
- starts with one of the package's own directories (`framework/costs.py`)
  and ends in a source suffix or a slash (`engine/tick` is a span's name,
  `native/ptpu_predict` a build product);
- is a bare `.py`, `.md` or `.sh` file name (`gone.py`, `trace_merge.py`:
  it must be some file's name in the tree; a bare `.cc` is the reference
  project's), or an upper-case record at the root (`GONE_RECORD.json`).

`::TestName`, `:123` and `:function` tails are cut; a token holding `*`,
`<`, `{` or `...` is a pattern, not a path."""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT) for p in
                      glob.glob(os.path.join(ROOT, "docs", "*.md"))))
TOP = ("tools/", "benchmark/", "paddle_tpu/", "tests/", "docs/")
SOURCE = (".py", ".md", ".sh", ".cc", ".h", ".json", ".jsonl", ".toml")
_TOKEN = re.compile(r"`([^`\s]+)`")


@pytest.fixture(scope="module")
def tree():
    """-> (the package's directories as prefixes, every file name in the
    tree): what `missing_paths` looks paths up in."""
    pkg = os.path.join(ROOT, "paddle_tpu")
    package_dirs = tuple(d + "/" for d in sorted(os.listdir(pkg))
                         if os.path.isdir(os.path.join(pkg, d))
                         and not d.startswith("__"))
    basenames = set(os.listdir(ROOT))
    for top in TOP + ("examples/",):
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            basenames.update(files)
    return package_dirs, basenames


def _exists(path):
    """`path` or, for `tests/axk1_tiny.gaps`, the module it is an attribute
    of."""
    full = os.path.join(ROOT, path)
    return os.path.exists(full) or (
        "." in os.path.basename(path)
        and os.path.exists(full.rsplit(".", 1)[0] + ".py"))


def missing_paths(text, package_dirs, basenames):
    """The repository paths `text` names in backticks that do not exist."""
    missing = []
    for token in _TOKEN.findall(text):
        if any(c in token for c in "*<{(") or "..." in token:
            continue
        path = re.split(r"::|:(?=[A-Za-z_0-9])", token)[0].rstrip(".,;")
        if path.startswith(TOP):
            ok = _exists(path)
        elif path.startswith(package_dirs):
            if not path.endswith(SOURCE + ("/",)):
                continue
            ok = _exists(os.path.join("paddle_tpu", path))
        elif "/" in path:
            continue
        elif path.endswith((".py", ".md", ".sh")) or (
                path.endswith((".json", ".jsonl")) and path[0].isupper()):
            ok = path in basenames
        else:
            continue
        if not ok:
            missing.append(token)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document, tree):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    assert missing_paths(text, *tree) == []


def test_the_check_sees_a_deleted_file_in_each_form(tree):
    """The reader itself: a deleted harness is found under each way a
    document may name it, and what is no path is left alone."""
    text = ("`tools/gone_harness.py` `gone.py` `GONE_RECORD_r05.json` "
            "`framework/no_such_module.py` `tests/no_such_test.py::TestX` "
            "`engine/tick` `native/ptpu_predict` `tools/gone_*.py` "
            "`tools/lint_program.py` `framework/costs.py:37` `PERF.md` "
            "`tests/tiny_engines.weights` `train_meta.json`")
    assert missing_paths(text, *tree) == [
        "tools/gone_harness.py", "gone.py", "GONE_RECORD_r05.json",
        "framework/no_such_module.py", "tests/no_such_test.py::TestX"]
