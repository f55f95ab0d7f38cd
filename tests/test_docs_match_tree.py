"""The documents that describe the tree as it is name files and verbs
that exist.

``README.md``, ``ARCHITECTURE.md``, ``MIGRATION.md`` and ``BASELINE.md``
say what the repository holds today; a path or a CLI verb in them that
has gone sends a reader nowhere. ``PERF.md``, ``ROADMAP.md`` and
``CHANGES.md`` are histories and rightly name what went, so they are not
held to this.
"""

from __future__ import annotations

import fnmatch
import os
import re
import signal

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "ARCHITECTURE.md", "MIGRATION.md", "BASELINE.md")

# files of the reference repository (ruivieira/ccfd-demo-summit), which
# MIGRATION.md and BASELINE.md cite by the reference's own paths
REFERENCE_FILES = frozenset({
    "router.yaml", "ccd-service.yaml", "frauddetection_cr.yaml",
    "ProducerDeployment.yaml", "modelfull-route.yaml", "modelfull.json",
    "notification-service.yaml",
})
# written into a state directory by a running platform, never committed
RUNTIME_FILES = frozenset({"versions.json"})

_BACKTICKED = re.compile(r"`([^`\s]+)`")
_SUFFIX = re.compile(r"(::[\w.]+|:[\d,-]+)$")
_LOOKS_LIKE_A_FILE = re.compile(
    r"^[\w./*-]+\.(py|json|jsonl|md|sh|yaml)(::[\w.]+|:[\d,-]+)?$")


@pytest.fixture(scope="module")
def tree() -> list[str]:
    """Every file of the checkout, by its path from the root, without the
    directories ``.gitignore`` lists (build outputs, caches, what a chip
    run brings back) and without hidden ones."""
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    found = []
    for where, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d not in ignored and not d.startswith(".")]
        found += [os.path.relpath(os.path.join(where, f), REPO) for f in files]
    return found


def _cited_paths(doc: str) -> set[str]:
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    return {_SUFFIX.sub("", m.group(1)) for m in _BACKTICKED.finditer(text)
            if _LOOKS_LIKE_A_FILE.match(m.group(1))}


def _exists(path: str, tree: list[str]) -> bool:
    """A cited path is written from the root, from the package, or as the
    tail of a longer one (``serving/history.py``); a ``*`` in it has to
    match at least one file."""
    if os.path.basename(path) in REFERENCE_FILES | RUNTIME_FILES:
        return True
    path = path.lstrip("/")
    return any(fnmatch.fnmatchcase(f, path) or fnmatch.fnmatchcase(f, "*/" + path)
               for f in tree)


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_is_in_the_tree(doc, tree):
    cited = _cited_paths(doc)
    assert cited, f"{doc} names no file at all: the pattern reads nothing"
    missing = sorted(p for p in cited if not _exists(p, tree))
    assert not missing, f"{doc} names files the tree does not hold: {missing}"


def _readme_verbs() -> list[str]:
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        return sorted(set(re.findall(r"python -m ccfd_tpu ([a-z]+)", f.read())))


def _main_exit_status(argv: list[str]) -> int:
    from ccfd_tpu import cli

    # a service verb maps SIGTERM to KeyboardInterrupt before it parses;
    # the test's process keeps its own handler
    before = signal.getsignal(signal.SIGTERM)
    try:
        with pytest.raises(SystemExit) as stop:
            cli.main(argv)
    finally:
        signal.signal(signal.SIGTERM, before)
    return stop.value.code


@pytest.mark.parametrize("verb", _readme_verbs())
def test_every_verb_the_readme_shows_is_one_the_parser_takes(verb, capsys):
    assert _main_exit_status([verb, "--help"]) == 0
    assert verb in capsys.readouterr().out


def test_the_pre_chip_benchmark_verb_is_refused(capsys):
    assert "bench" not in _readme_verbs()
    assert _main_exit_status(["bench"]) == 2
    assert "invalid choice: 'bench'" in capsys.readouterr().err
