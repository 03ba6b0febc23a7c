"""Docs subsystem checks, kept in the required fast tier: the public
API's docstring examples run under doctest, every relative link in
README.md and docs/ resolves, and the paper-to-code table covers every
core/ module."""

import doctest
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

from repro.core import analytical, fusion, scheduler, spacegen, workload
from repro.lower import cache as lower_cache

#: Modules whose ``>>>`` examples are part of the documented API
#: (mirrors the `docs` CI job's ``python -m doctest`` invocation).
DOCTEST_MODULES = (workload, spacegen, fusion, scheduler, analytical,
                   lower_cache)


def test_docstring_examples_run():
    for mod in DOCTEST_MODULES:
        failures, _ = doctest.testmod(mod, verbose=False)
        assert failures == 0, f"doctest failures in {mod.__name__}"


def test_markdown_links_resolve():
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_links
    finally:
        sys.path.pop(0)
    problems = check_links.check_files(
        [REPO / "README.md", REPO / "docs"], REPO)
    assert problems == []


def test_architecture_table_covers_every_core_module():
    """docs/architecture.md's paper-to-code tables must reference every
    module of src/repro/core/ (acceptance criterion)."""
    text = (REPO / "docs" / "architecture.md").read_text()
    core = REPO / "src" / "repro" / "core"
    missing = [p.name for p in sorted(core.glob("*.py"))
               if p.name != "__init__.py" and p.name not in text]
    assert missing == []


def test_readme_names_the_three_entry_points():
    text = (REPO / "README.md").read_text()
    for needle in ("fusion.explore", "phase_schedule",
                   "select_schedule", "docs/architecture.md",
                   "pip install -e .[test]"):
        assert needle in text, f"README.md must mention {needle}"


def test_doc_snippets_match_source_verbatim():
    """Annotated code fences in docs/ (e.g. docs/serving.md's
    continuous-batching quickstart) must be verbatim contiguous regions
    of the source file they name (mirrors the docs CI job's
    ``python tools/check_snippets.py docs``)."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_snippets
    finally:
        sys.path.pop(0)
    problems = check_snippets.check_files([REPO / "docs"], REPO)
    assert problems == []
    # the checker itself must catch drift (guards against a regex
    # change silently matching nothing)
    assert not check_snippets.snippet_in_file(
        "this line is nowhere in quickstart\n",
        (REPO / "examples" / "quickstart.py").read_text())


def test_bench_diff_reports_polarity_aware_deltas():
    """tools/bench_diff.py: rows matched by name, per-field deltas, and
    throughput (tokens_s) counted as better-up while wall-clock (_ms)
    counts as better-down."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import bench_diff
    finally:
        sys.path.pop(0)
    base = {"rows": [{"name": "r", "step_ms": 10.0, "tokens_s": 4.0},
                     {"name": "gone", "x": 1}]}
    cur = {"rows": [{"name": "r", "step_ms": 12.0, "tokens_s": 5.0},
                    {"name": "new", "x": 1}]}
    d = bench_diff.diff_artifacts(base, cur)
    assert d["added"] == ["new"] and d["removed"] == ["gone"]
    (row,) = d["rows"]
    assert row["deltas"]["step_ms"]["pct"] == 20.0
    assert row["deltas"]["tokens_s"]["delta"] == 1.0
    assert bench_diff.field_polarity("step_ms") == -1
    assert bench_diff.field_polarity("tokens_s") == 1
    # the committed baseline snapshot stays diffable against itself
    snap = REPO / "benchmarks" / "baselines" / "BENCH_lowering.json"
    same = json.loads(snap.read_text())
    self_diff = bench_diff.diff_artifacts(same, same)
    assert all(not r["deltas"] for r in self_diff["rows"])
    assert bench_diff.regressions(self_diff, ["tokens_s"], 0.0) == []
