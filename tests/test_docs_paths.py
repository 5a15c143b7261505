"""Every repo path README.md and MIGRATION.md name in backticks exists."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a backticked token with a slash is a repo path when it starts here
ROOTS = ("paddle_tpu/", "tests/", "tools/", "benchmark/")
#: a bare upper-case name is a file at the root (``PERF.md``,
#: ``BENCHMARK.json``); a bare script name is a module somewhere
ROOT_FILE = re.compile(r"^[A-Z][A-Z0-9_]*(_r\d\d)?\.(md|json|jsonl|txt)$")
SCRIPT = re.compile(r"^\w+\.(py|sh)$")


def _basenames():
    names = set()
    for top in ("",) + ROOTS:
        for _, dirs, files in os.walk(os.path.join(REPO, top)):
            names.update(files)
            if not top:
                break                   # the root itself, not its tree
            dirs[:] = [d for d in dirs if d != "__pycache__"]
    return names


def _missing(text):
    known = _basenames()
    for token in sorted(set(re.findall(r"`([^`\s]+)`", text))):
        token = token.split("::")[0].split(":")[0].rstrip(".,;)")
        if any(c in token for c in "*<>{}$"):
            continue                    # a pattern, not a path
        if token.startswith(ROOTS) or ROOT_FILE.match(token):
            if not os.path.exists(os.path.join(REPO, token)):
                yield token
        elif SCRIPT.match(token) and token not in known:
            yield token


@pytest.mark.parametrize("doc", ["README.md", "MIGRATION.md"])
def test_backticked_repo_paths_exist(doc):
    with open(os.path.join(REPO, doc)) as f:
        missing = list(_missing(f.read()))
    assert not missing, f"{doc} points at files that are gone: {missing}"
