"""Docs hygiene: every relative markdown link in README/docs/reports
resolves to a real file, every docs page is indexed in docs/README.md,
and no page references modules deleted from the tree.

Doubles as the CI link-check (the workflow runs this file after the
benchmark steps so freshly generated reports/*.md are covered too).
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ``[text](target)`` — target split from an optional title/anchor.
_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)(?:\s+\"[^\"]*\")?\)")

#: Modules that used to exist; docs must not point at them anymore.
_DELETED = ("benchmarks/roofline.py", "benchmarks.roofline",
            "benchmarks/perf_bench.py", "benchmarks.perf_bench",
            "BENCH_xdes.json")


def _md_files():
    paths = [os.path.join(REPO, "README.md")]
    for sub in ("docs", "reports"):
        d = os.path.join(REPO, sub)
        if os.path.isdir(d):
            paths += sorted(os.path.join(d, f) for f in os.listdir(d)
                            if f.endswith(".md"))
    return paths


def _relative_links(path):
    text = open(path, encoding="utf-8").read()
    for target in _LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        yield target.split("#", 1)[0]


@pytest.mark.parametrize("path", _md_files(),
                         ids=lambda p: os.path.relpath(p, REPO))
def test_relative_links_resolve(path):
    base = os.path.dirname(path)
    missing = []
    for target in _relative_links(path):
        if not target:
            continue
        resolved = os.path.normpath(os.path.join(base, target))
        if not os.path.exists(resolved):
            missing.append(target)
    assert not missing, (
        f"{os.path.relpath(path, REPO)} has dead relative links: {missing}")


@pytest.mark.parametrize("path", _md_files(),
                         ids=lambda p: os.path.relpath(p, REPO))
def test_no_references_to_deleted_modules(path):
    text = open(path, encoding="utf-8").read()
    hits = [d for d in _DELETED if d in text]
    assert not hits, (
        f"{os.path.relpath(path, REPO)} references deleted modules: {hits}")


def test_docs_index_lists_every_page():
    index = os.path.join(REPO, "docs", "README.md")
    assert os.path.exists(index), "docs/README.md index is missing"
    text = open(index, encoding="utf-8").read()
    pages = [f for f in os.listdir(os.path.join(REPO, "docs"))
             if f.endswith(".md") and f != "README.md"]
    unlisted = [p for p in pages if p not in text]
    assert not unlisted, f"docs/README.md does not link: {unlisted}"
