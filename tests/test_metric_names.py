# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Metric-name drift guard (docs parsed and machine-checked against
the code, applied to series names): every ``bluefog.*`` series emitted anywhere
in ``bluefog_tpu/`` must appear in the docs/metrics.md series-reference
table, and every table row must correspond to a name the code can
actually emit. A dashboard built from the docs must never silently
diverge from the runtime.

Extraction is static: double-quoted ``"bluefog...."`` string literals
(the package's uniform idiom for series names), with f-string
``{expr}`` segments and the docs' ``<x>`` segments both treated as
wildcards. A literal that other literals extend with a dot (e.g. the
``"bluefog.gossip"`` drain prefix) is a *namespace*: the table must
hold at least one row under it, and rows under it are considered
emittable.
"""

import fnmatch
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "bluefog_tpu")
DOC = os.path.join(REPO, "docs", "metrics.md")

_LITERAL_RE = re.compile(r'f?"(bluefog\.[^"\n]*)"')


def _code_patterns():
    """All ``bluefog.*`` string literals in the package, f-string
    placeholders normalized to ``*``; returns (names, namespaces)."""
    raw = set()
    for path in glob.glob(PKG + "/**/*.py", recursive=True):
        with open(path) as f:
            src = f.read()
        for m in _LITERAL_RE.finditer(src):
            raw.add(re.sub(r"\{[^}]*\}", "*", m.group(1)))
    namespaces = {
        r for r in raw
        if any(o.startswith(r + ".") for o in raw if o != r)
    }
    return raw - namespaces, namespaces


def _doc_patterns():
    """Series names from the reference table between the markers,
    ``<x>`` segments normalized to ``*``."""
    text = open(DOC).read()
    m = re.search(
        r"<!-- series-reference:begin -->(.*?)"
        r"<!-- series-reference:end -->",
        text, re.S,
    )
    assert m, "docs/metrics.md lost its series-reference markers"
    names = set()
    for row in re.finditer(r"^\|\s*`([^`]+)`", m.group(1), re.M):
        names.add(re.sub(r"<[^>]*>", "*", row.group(1)))
    assert names, "series-reference table is empty"
    return names


def _matches(a: str, b: str) -> bool:
    """Two wildcarded names denote the same series family if either
    pattern covers the other (wildcards on the opposite side are
    treated as a plain token)."""
    return (
        a == b
        or fnmatch.fnmatchcase(a.replace("*", "X"), b)
        or fnmatch.fnmatchcase(b.replace("*", "X"), a)
    )


def test_every_emitted_series_is_documented():
    code, namespaces = _code_patterns()
    docs = _doc_patterns()
    undocumented = sorted(
        c for c in code if not any(_matches(c, d) for d in docs)
    )
    assert not undocumented, (
        "series emitted in bluefog_tpu/ but missing from the "
        f"docs/metrics.md reference table: {undocumented}"
    )
    for ns in sorted(namespaces):
        assert any(d.startswith(ns + ".") for d in docs), (
            f"namespace prefix {ns!r} has no documented series under it"
        )


def test_every_documented_series_is_emitted():
    code, namespaces = _code_patterns()
    docs = _doc_patterns()
    phantom = sorted(
        d for d in docs
        if not any(_matches(d, c) for c in code)
        # a namespace literal is itself emittable (e.g. the
        # "bluefog.allgather.quant_err" gauge, extended by its ".max"
        # sibling), and rows under a namespace are runtime-composed
        # (the drain-prefix gauges)
        and d not in namespaces
        and not any(d.startswith(ns + ".") for ns in namespaces)
    )
    assert not phantom, (
        "docs/metrics.md reference table rows with no emitting code "
        f"in bluefog_tpu/: {phantom}"
    )


def test_guard_extraction_sees_known_anchors():
    """The guard itself must be looking at real data: a known literal,
    a known f-string family, and a known namespace must all surface."""
    code, namespaces = _code_patterns()
    assert "bluefog.recompiles" in code
    assert "bluefog.doctor.advisory.*" in code
    assert "bluefog.gossip" in namespaces
