"""Serving driver: continuous-batching loop over the per-slot engine.

Requests of different prompt lengths are prefilled on the side
(chunked, interleaved with decode) and inserted into free batch rows
mid-stream; every decode step is one whole-batch launch whose per-row
``cache_len`` feeds the masked kernels.  ``--paged`` serves from a KV
page pool instead of dense per-row caches; ``--layers`` cuts a
published config to its first layers (widths unchanged).  The last line
printed is the engine's and scheduler's counters and each span's total
self time (``serve/tracing.py``).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --requests 6 --max-new 16

:func:`make_engine` and :func:`serve` are the entry point's two halves;
``chip_smoke.py`` drives the same pair at published widths on a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import transformer as tf
from repro.serve import (ContinuousBatchingEngine,
                         PagedContinuousBatchingEngine, Request,
                         RequestBatcher, make_serving_plan, tracing)
from repro.serve.engine import pool_pages


def make_engine(params, cfg, *, batch: int, max_len: int,
                prefill_chunk: Optional[int], paged: bool = False,
                page_size: int = 16, plan: bool = True, mesh=None,
                engine_cls=None):
    """The continuous-batching engine this entry point serves with:
    plan-driven (``plan=False`` keeps the config's own kernel choice),
    dense or ``paged`` (a pool sized for every row at ``max_len``, plus
    the reserved null page, or where two of those do not fit beside the
    weights, what fits twice: ``serve.engine.pool_pages``).  With ``mesh`` the KV cache is placed on
    it by its logical axes; the caller places the parameters and serves
    under ``sharding.set_rules_for_mesh(mesh)``.  ``engine_cls``
    substitutes a subclass of the dense or paged engine."""
    dtype = jnp.dtype(cfg.compute_dtype)
    sp = make_serving_plan(cfg, max_len, paged=paged,
                           page_size=page_size if paged else None) \
        if plan else None
    kw = dict(batch_size=batch, max_len=max_len, plan=sp, dtype=dtype,
              prefill_chunk=prefill_chunk)
    if paged:
        cls = engine_cls or PagedContinuousBatchingEngine
        eng = cls(params, cfg, page_size=page_size,
                  num_pages=pool_pages(cfg, batch, max_len, page_size,
                                       dtype, params), **kw)
    else:
        cls = engine_cls or ContinuousBatchingEngine
        eng = cls(params, cfg, **kw)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.sharding import logical_to_mesh_axes
        cache = jax.tree.map(
            lambda axes, a: NamedSharding(mesh, logical_to_mesh_axes(
                axes, mesh=mesh, shape=a.shape)),
            tf.cache_axes(cfg), eng.state.cache,
            is_leaf=lambda x: isinstance(x, tuple))
        rest = NamedSharding(mesh, PartitionSpec())
        eng.state = jax.device_put(eng.state, dataclasses.replace(
            jax.tree.map(lambda _: rest, eng.state), cache=cache))
    return eng


def make_requests(cfg, lengths, max_new: int, seed: int = 0) -> list:
    """One request per prompt length, tokens drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [Request(uid=uid, prompt=rng.integers(
                0, cfg.vocab_size, size=int(n)).tolist(),
                    max_new_tokens=max_new)
            for uid, n in enumerate(lengths)]


def serve(engine, requests, max_len: int) -> list:
    """Serve ``requests`` to completion on ``engine`` through a fresh
    :class:`RequestBatcher`; returns the finished requests."""
    batcher = RequestBatcher(engine.batch_size, max_len=max_len)
    for r in requests:
        batcher.submit(r)
    steps = sum(len(r.prompt) + r.max_new_tokens for r in requests)
    return batcher.serve(engine, max_steps=steps)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="keep the first N layers (widths unchanged)")
    ap.add_argument("--paged", action="store_true",
                    help="serve from a KV page pool")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    args = ap.parse_args(argv)

    from repro.launch.compilation import enable_compile_cache
    enable_compile_cache()
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    eng = make_engine(params, cfg, batch=args.batch, max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk, paged=args.paged,
                      page_size=args.page_size)
    rng = np.random.default_rng(0)
    reqs = make_requests(cfg, rng.integers(4, 12, size=args.requests),
                         args.max_new)

    t0 = time.time()
    finished = serve(eng, reqs, args.max_len)
    dt = time.time() - t0
    total_tokens = sum(len(r.generated) for r in finished)
    print(f"served {len(finished)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/max(dt,1e-9):.1f} tok/s, "
          f"compilation included)")
    if eng.plan is not None:
        paths = {p for (ph, _, _, p, _) in eng.plan.resolutions
                 if ph == "decode"}
        print(f"decode kernel paths used: {sorted(paths)}")
    for r in finished[:3]:
        print(f"  req {r.uid}: prompt {len(r.prompt)} toks -> "
              f"{r.generated[:8]}...")
    print(tracing.summary())


if __name__ == "__main__":
    main()
