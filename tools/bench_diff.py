#!/usr/bin/env python3
"""Diff two ``BENCH_<area>.json`` artifacts (no dependencies): rows are
matched by ``name`` and every shared numeric field is reported as an
absolute and relative delta, so a bench regression shows up as one
readable line per metric instead of a JSON eyeball-diff.

    python tools/bench_diff.py benchmarks/baselines/BENCH_lowering.json \\
        BENCH_lowering.json

Rows present on only one side are listed as added/removed.  With
``--fail-over PCT`` the exit code is non-zero when any field named by
``--watch`` (repeatable; substring match, e.g. ``tokens_s`` or
``_ms``) moved against its polarity by more than PCT percent —
``*_ms``/``*_s``-suffixed wall-clock fields regress upward, everything
else (tokens/s, speedups, fractions) regresses downward.  Also
importable — ``diff_artifacts(a, b)`` returns the delta rows (used by
tests/test_docs.py to keep the tool in the fast tier).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

# wall-clock/footprint fields: bigger is worse.  NOT bare "_s" — the
# artifacts' throughput fields are spelled tokens_s (tokens/second).
_COST_SUFFIXES = ("_ms", "_us", "_seconds", "_bytes", "_words")


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _rows(artifact: dict) -> dict:
    return {r["name"]: r for r in artifact.get("rows", [])}


def field_polarity(field: str) -> int:
    """+1 if bigger is better (throughput, speedup), -1 if bigger is
    worse (wall-clock, memory)."""
    return -1 if field.endswith(_COST_SUFFIXES) else 1


def diff_artifacts(a: dict, b: dict) -> dict:
    """Structured diff of two artifact dicts (``a`` = baseline).

    Returns ``{"rows": [...], "added": [...], "removed": [...]}`` where
    each row is ``{"name", "deltas": {field: {"base", "new", "delta",
    "pct"}}}`` over the shared numeric fields that changed.
    """
    ra, rb = _rows(a), _rows(b)
    out = {"rows": [], "added": sorted(rb.keys() - ra.keys()),
           "removed": sorted(ra.keys() - rb.keys())}
    for name in sorted(ra.keys() & rb.keys()):
        deltas = {}
        for field in ra[name]:
            va, vb = ra[name][field], rb[name].get(field)
            if not (_numeric(va) and _numeric(vb)) or va == vb:
                continue
            pct = (vb - va) / abs(va) * 100 if va else float("inf")
            deltas[field] = {"base": va, "new": vb,
                             "delta": round(vb - va, 4),
                             "pct": round(pct, 2)}
        out["rows"].append({"name": name, "deltas": deltas})
    return out


def regressions(diff: dict, watch: list[str], fail_over: float) -> list[str]:
    """Watched fields that moved against their polarity by > fail_over%."""
    bad = []
    for row in diff["rows"]:
        for field, d in row["deltas"].items():
            if watch and not any(w in field for w in watch):
                continue
            if field_polarity(field) * d["pct"] < -fail_over:
                bad.append(f"{row['name']}.{field}: {d['base']} -> "
                           f"{d['new']} ({d['pct']:+.1f}%)")
    return bad


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="diff two BENCH_<area>.json artifacts")
    parser.add_argument("baseline", type=Path)
    parser.add_argument("current", type=Path)
    parser.add_argument("--watch", action="append", default=[],
                        help="field substring to gate on (repeatable)")
    parser.add_argument("--fail-over", type=float, default=None,
                        metavar="PCT",
                        help="exit non-zero when a watched field "
                             "regresses by more than PCT percent")
    args = parser.parse_args(argv)
    a = json.loads(args.baseline.read_text())
    b = json.loads(args.current.read_text())
    diff = diff_artifacts(a, b)

    for name in diff["removed"]:
        print(f"- {name} (only in baseline)")
    for name in diff["added"]:
        print(f"+ {name} (new row)")
    for row in diff["rows"]:
        if not row["deltas"]:
            print(f"= {row['name']}: no numeric change")
            continue
        print(row["name"])
        for field, d in row["deltas"].items():
            arrow = "better" if field_polarity(field) * d["pct"] > 0 \
                else "worse"
            print(f"    {field:28s} {d['base']:>12} -> {d['new']:>12} "
                  f"({d['pct']:+.1f}%, {arrow})")

    if args.fail_over is not None:
        bad = regressions(diff, args.watch, args.fail_over)
        for line in bad:
            print(f"REGRESSION {line}", file=sys.stderr)
        print(f"bench_diff: {len(bad)} regression(s) over "
              f"{args.fail_over}%")
        return 1 if bad else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
