"""The seeded traffic generator (bench/harness/traffic.py): deterministic,
every length inside its clips and on the grid, the same requests at the
same times for every seed, Poisson mixes' plans as they were recorded,
and Gamma arrivals at the asked mean and coefficient of variation."""

import hashlib
import json
import pathlib

import numpy as np
import pytest

from harness import traffic

MIXES = sorted((pathlib.Path(__file__).parents[2] / "bench" / "traffic")
               .glob("*.json"))
BIG = 2 ** 33 + 12345          # the driver's seeds exceed 32 bits
#: digests of the Poisson and closed-loop mixes' plans at seed BIG,
#: recorded before mixes could name an arrival process
RECORDED = json.loads((pathlib.Path(__file__).parent / "data"
                       / "hashes_before_arch_move.json").read_text())["plans"]


def load(name):
    return json.loads((MIXES[0].parent / f"{name}.json").read_text())


@pytest.fixture(params=[p.stem for p in MIXES])
def mix(request):
    return load(request.param)


def plan(mix, seed):
    m = dict(mix, pool=min(int(mix["pool"]), 120))
    return traffic.make_plan(m, seed, 1000, int(mix["engine"]["max_len"]))


def test_deterministic(mix):
    a, b = plan(mix, BIG), plan(mix, BIG)
    for x, y in zip(a.fill + a.stream, b.fill + b.stream):
        assert (x.prompt, x.max_new, x.due) == (y.prompt, y.max_new, y.due)


def test_seed_changes_tokens_not_sizes_or_times(mix):
    a, b = plan(mix, BIG), plan(mix, 7)
    assert [(len(r.prompt), r.max_new, r.due) for r in a.fill + a.stream] \
        == [(len(r.prompt), r.max_new, r.due) for r in b.fill + b.stream]
    assert any(x.prompt != y.prompt for x, y in zip(a.stream, b.stream))


def test_lengths_inside_clips_and_on_grid(mix):
    p = plan(mix, 3)
    grid, max_len = int(mix["grid"]), int(mix["engine"]["max_len"])
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    for r in p.stream:
        assert lo <= len(r.prompt) <= hi and len(r.prompt) % grid == 0
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert len(r.prompt) + r.max_new <= max_len
        assert all(0 <= t < 1000 for t in r.prompt)
    assert len(p.fill) == int(mix["fill_rows"])
    for r in p.fill:
        assert len(r.prompt) % grid == 0 and r.max_new >= 1
        assert len(r.prompt) + r.max_new <= max_len


def test_open_loop_arrivals(mix):
    p = plan(mix, 3)
    if mix["loop"] != "open":
        assert all(r.due is None for r in p.stream)
        assert p.clients == int(mix["clients"]) == len(p.fill)
        return
    due = [r.due for r in p.stream]
    assert due == sorted(due) and due[0] > 0
    gaps = np.diff([0.0] + due)
    # Poisson: mean gap near 1/rate over the pool
    assert abs(gaps.mean() * float(mix["rate"]) - 1) < 0.35


def test_lengths_follow_the_distribution():
    rng = np.random.default_rng(0)
    spec = {"dist": "lognormal", "median": 768, "sigma": 0.8, "min": 64,
            "max": 1536}
    x = traffic.draw_lengths(spec, 20000, rng, grid=64)
    assert x.min() >= 64 and x.max() <= 1536 and (x % 64 == 0).all()
    assert abs(np.median(x) - 768) <= 64
    u = traffic.draw_lengths({"dist": "uniform", "min": 256, "max": 1024},
                             20000, rng)
    assert u.min() >= 256 and u.max() <= 1024
    assert abs(u.mean() - 640) < 10


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_plans_as_recorded(name):
    mix = load(name)
    p = traffic.make_plan(mix, BIG, 1000, int(mix["engine"]["max_len"]))
    sizes, tokens = hashlib.sha256(), hashlib.sha256()
    for r in p.fill + p.stream:
        sizes.update(f"{len(r.prompt)} {r.max_new} {r.due!r} {r.fill};"
                     .encode())
        tokens.update(np.asarray(r.prompt, np.int64).tobytes())
    assert sizes.hexdigest() == RECORDED[name]["sizes_and_times"]
    assert tokens.hexdigest() == RECORDED[name]["tokens"]


def test_gamma_arrivals_keep_mean_and_cv():
    mixes = [m for m in map(load, (p.stem for p in MIXES))
             if m.get("arrivals", {}).get("dist") == "gamma"]
    assert mixes
    for mix in mixes:
        p = traffic.make_plan(mix, 3, 1000, int(mix["engine"]["max_len"]))
        gaps = np.diff([0.0] + [r.due for r in p.stream])
        assert len(gaps) == int(mix["pool"])
        assert abs(gaps.mean() * float(mix["rate"]) - 1) <= 0.10
        cv = gaps.std() / gaps.mean()
        assert abs(cv / float(mix["arrivals"]["cv"]) - 1) <= 0.15
