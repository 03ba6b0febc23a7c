"""The one traffic generator: every mix is a JSON file of parameters.

A mix gives the loop (``open``: arrivals at a mean ``rate`` requests a
second, Poisson unless ``arrivals`` names another process; ``closed``:
``clients`` callers that each send their next request when the last one
finishes), the prompt and output length
distributions, the grid prompt lengths sit on, and how many rows the
load holds in steady state (``fill_rows``).

Sizes and arrival times come from the mix's own ``pool_seed``, and
``--seed`` draws the token ids (and, elsewhere, the weights).  So every
seed offers the same requests at the same times, and the work a run
does does not hang on the seed: at the step times this program has, a
window admits a handful of new requests, and the tokens they add would
follow the draw more than the system.

Steady-state rows (``fill``) are requests already part-way through: the
output length is drawn length-biased (a row in steady state more likely
holds a long request), the number already generated uniformly below it,
and the request keeps the rest of its budget.  Their "prompt" is the
drawn prompt plus the tokens already generated.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Req:
    uid: int
    prompt: list
    max_new: int
    due: Optional[float] = None     # open loop: seconds after window start
    fill: bool = False              # steady-state row, prefilled in set-up


def _grid(n: np.ndarray, grid: int, lo: int, hi: int) -> np.ndarray:
    """Round to the nearest multiple of ``grid`` inside [lo, hi] (both
    rounded onto the grid too)."""
    lo_g, hi_g = -(-lo // grid) * grid, hi // grid * grid
    return np.clip(np.rint(n / grid) * grid, lo_g, hi_g).astype(int)


def draw_lengths(spec: dict, n: int, rng: np.random.Generator,
                 grid: int = 1) -> np.ndarray:
    """``n`` lengths from ``spec``: ``uniform`` over [min, max] or
    ``lognormal`` with ``median`` and ``sigma``, clipped to [min, max]
    and put on ``grid``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        x = rng.uniform(lo, hi, size=n)
    elif spec["dist"] == "lognormal":
        x = float(spec["median"]) * np.exp(
            float(spec["sigma"]) * rng.standard_normal(n))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return _grid(np.clip(x, lo, hi), grid, lo, hi)


def arrival_gaps(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps between open-loop arrivals at the mix's mean ``rate``.
    Without ``arrivals``, or with ``{"dist": "exponential"}``, they are
    exponential (Poisson arrivals); with ``{"dist": "gamma", "cv": c}``
    Gamma with shape 1/c^2 and scale c^2/rate: the mean gap stays 1/rate
    and c is the gaps' coefficient of variation (c > 1: bursts)."""
    rate = float(mix["rate"])
    spec = mix.get("arrivals", {"dist": "exponential"})
    if spec["dist"] == "exponential":
        return rng.exponential(1.0 / rate, size=n)
    if spec["dist"] == "gamma":
        cv2 = float(spec["cv"]) ** 2
        return rng.gamma(1.0 / cv2, cv2 / rate, size=n)
    raise ValueError(f"unknown arrival process {spec['dist']!r}")


@dataclasses.dataclass
class Plan:
    fill: list                      # steady-state rows, prefilled in set-up
    stream: list                    # window requests, in send order
    loop: str
    clients: int = 0


def make_plan(mix: dict, seed: int, vocab: int, max_len: int) -> Plan:
    """The requests one run offers, from the mix and ``--seed``."""
    pool_rng = np.random.default_rng(int(mix["pool_seed"]))
    grid = int(mix.get("grid", 1))
    n = int(mix["pool"])
    prompts = draw_lengths(mix["prompt"], n, pool_rng, grid)
    outputs = draw_lengths(mix["output"], n, pool_rng)
    n_fill = int(mix["fill_rows"])
    f_prompt = draw_lengths(mix["prompt"], n_fill, pool_rng)
    # length-biased output lengths and uniform ages for the rows held
    # in steady state
    cand = draw_lengths(mix["output"], 64 * n_fill, pool_rng)
    f_out = pool_rng.choice(cand, size=n_fill, p=cand / cand.sum())
    f_age = (pool_rng.uniform(size=n_fill) * f_out).astype(int)
    gaps = None
    if mix["loop"] == "open":
        gaps = arrival_gaps(mix, n, pool_rng)

    rng = np.random.default_rng(seed)
    uid = 0
    fill = []
    for i in range(n_fill):
        ctx = int(_grid(np.array([f_prompt[i] + f_age[i]]), grid,
                        grid, max_len - 1)[0])
        rest = max(1, min(int(f_out[i] - f_age[i]), max_len - ctx))
        fill.append(Req(uid, rng.integers(0, vocab, ctx).tolist(), rest,
                        fill=True))
        uid += 1
    stream = []
    due = 0.0
    for i in range(n):
        p = int(prompts[i])
        out = max(1, min(int(outputs[i]), max_len - p))
        r = Req(uid, rng.integers(0, vocab, p).tolist(), out)
        if gaps is not None:
            due += float(gaps[i])
            r.due = due
        stream.append(r)
        uid += 1
    return Plan(fill=fill, stream=stream, loop=mix["loop"],
                clients=int(mix.get("clients", 0)))

