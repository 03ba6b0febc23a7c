"""Measured-vs-predicted validation of the DSE cost model through the
lowering subsystem (the paper's Sec. III methodology — Stream
predictions vs measured GAP8 runs — re-run against this repo's own
jax_pallas runtime).

For each (config, phase, shape) cell the harness:

1. lowers the candidate schedules — the DSE-chosen one plus forced
   counterfactuals (LBL, score-fusion, full fusion) — into
   ExecutionPlans (``repro.lower``),
2. *predicts* each plan with the analytical engine
   (``ExecutionPlan.predict`` -> cycles, peak words),
3. *executes* each plan's kernel path on real arrays (Pallas interpret
   mode on CI / CPU; native kernels on TPU) and wall-clocks it,
4. emits a paper-style validation table plus per-cell schedule-ranking
   agreement (is the predicted-faster schedule measured-faster?) and
   per-schedule shape-scaling agreement (do predicted and measured
   grow together?).

Decode cells are additionally run in the *serving regime* — a
``lengths`` mask over a KV cache — which now executes the masked
scalar-prefetch Pallas kernels on the Pallas path: the
``dse+lengths`` rows carry a ``lengths_downgrades`` count that must
be 0 (the planned kernel path is the executed path).  A second
serving-regime row per decode cell, ``megakernel``, forces the
``fuse_block`` counterfactual through the ``decode_block`` entry with
RoPE on — the one-launch decode sub-block (projection + RoPE + masked
attention + output projection + residual) against the composed
pipeline it replaces; qk-norm configs downgrade honestly and the row
labels whatever path actually ran.  Downgrades recorded on the plans
(qk-norm Q-fusion legality, entry rung-downs, residual masked-lengths
dtype gates) are printed with the table, so a measured number is
never attributed to a path that did not run.

Predicted cycles cover the full lowered block (attention + FFN; the
FFN term is identical across candidate schedules of one cell, so
schedule ranking is attention-driven); measured wall-clock isolates
the attention pipeline x -> (Q) -> scores -> out that the schedules
differ on.

The cost model's *memory* claim is validated the same
measured-vs-predicted way (printed after the latency cells; ``--memory``
runs it alone): a paged serving engine drives a request stream and at
every decode step the plan's ``predicted_kv_pages`` /
``predicted_kv_page_words`` over the live rows' contexts are compared
against the :class:`~repro.serve.engine.PageAllocator`'s actual
page-pool occupancy — per-step agreement plus the peak, next to the
dense ``batch * max_len`` allocation the pool replaces.  Preemptions
under page pressure are part of the run, so the agreement also covers
pages leaving and re-entering the pool.

    PYTHONPATH=src python tools/validate_costmodel.py
    PYTHONPATH=src python tools/validate_costmodel.py --memory
    PYTHONPATH=src python tools/validate_costmodel.py \
        --arch qwen3-8b --backend interpret --prefill-seq 128
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro import lower
from repro.kernels import ops

#: (schedule label, fuse_q, fuse_scores) counterfactual grid per phase.
#: None, None = let the phase decision rule pick (the DSE choice).
CANDIDATES = {
    "prefill": [("dse", None, None), ("lbl", False, False),
                ("fuse_pv", False, True), ("fuse_all", True, True)],
    "decode": [("dse", None, None), ("lbl", False, False),
               ("fuse_scores", False, True), ("fuse_all", True, True)],
}


def _dims(cfg):
    return (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model)


def _inputs(cfg, phase: str, n: int, key=None):
    """(x, wq, k, v, q_offset): the attention pipeline's inputs for one
    cell — M rows of new input vs an n-deep (self or cached) score
    width.  No RoPE/qk-norm, so every candidate path (including
    Q-projection fusion) is legal and the race is schedules-only;
    the serving-regime ``megakernel`` cell builds its own RoPE-on
    inputs."""
    hq, hkv, d, e = _dims(cfg)
    key = key if key is not None else jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    m = 1 if phase == "decode" else n
    x = jax.random.normal(ks[0], (1, m, e), jnp.float32)
    wq = jax.random.normal(ks[1], (e, hq, d), jnp.float32) / e ** 0.5
    k = jax.random.normal(ks[2], (1, hkv, n, d), jnp.float32)
    v = jax.random.normal(ks[3], (1, hkv, n, d), jnp.float32)
    return x, wq, k, v, n - m


def _candidate_fn(dispatch, causal: bool, q_offset: int):
    """One jit-able x,wq,k,v -> out pipeline taking the dispatch's
    kernel path (projection included, so every candidate does the same
    end-to-end math)."""
    if dispatch.path == lower.QPROJ_ATTENTION:
        def f(x, wq, k, v):
            return ops.qproj_attention(
                x, wq, k, v, causal=causal, q_offset=q_offset,
                plan=dispatch, interpret=dispatch.interpret)
    else:
        def f(x, wq, k, v):
            q = jnp.einsum("bse,ehd->bhsd", x, wq)
            return ops.attention(
                q, k, v, causal=causal, q_offset=q_offset,
                plan=dispatch, interpret=dispatch.interpret)
    return f


def _masked_cell(cfg, arch: str, n: int, jax_backend: str,
                 interpret: bool, repeats: int) -> dict:
    """The serving-regime decode cell: the DSE plan executed WITH a
    ``lengths`` mask over an n-deep cache (what every KV-cached serve
    step passes).  On the Pallas path this runs the masked
    scalar-prefetch kernel; ``lengths_downgrades`` must be 0."""
    plan = lower.lower(cfg, "decode", n, bucket=n)
    d = lower.dispatch(plan, backend=jax_backend, interpret=interpret,
                       entry="attention", lengths_masked=True)
    x, wq, k, v, _ = _inputs(cfg, "decode", n)
    lens = jnp.full((x.shape[0],), n, jnp.int32)

    def fn(x, wq, k, v):
        q = jnp.einsum("bse,ehd->bhsd", x, wq)
        return ops.attention(q, k, v, causal=True, lengths=lens,
                             plan=d, interpret=d.interpret)

    us = _measure_us(fn, (x, wq, k, v), repeats)
    pred = plan.predict()
    return {
        "name": f"{arch}_decode{n}_dse+lengths",
        "kind": "run", "arch": arch, "phase": "decode", "n": n,
        "schedule": "dse+lengths", "policy": plan.block(0).policy,
        "path": d.path, "impl": d.impl,
        "predicted_cycles": round(pred.latency_cycles),
        "predicted_peak_words": pred.peak_active_words,
        "measured_us": round(us, 1),
        "downgrades": [f"{g.from_path}->{g.to_path}: {g.reason}"
                       for g in plan.downgrades],
        "lengths_downgrades": sum(
            g.count for g in plan.downgrades
            if "masked-lengths" in g.reason),
    }


def _megakernel_cell(cfg, arch: str, n: int, jax_backend: str,
                     interpret: bool, repeats: int) -> dict:
    """The one-launch decode sub-block cell: the ``fuse_block``
    counterfactual lowered and dispatched through the ``decode_block``
    entry (the call site hands x, Wq, Wo AND the residual), RoPE on —
    the zoo regime the megakernel was built for.  On RoPE-only configs
    the dispatched path is ``decode_megakernel`` with an empty ledger;
    qk-norm configs rung down honestly and the row labels the path
    that actually ran.  The composed pipeline (qproj + output
    projection + residual add, same end-to-end math) is timed next to
    it so the row is a like-for-like launch-count comparison."""
    hq, hkv, d_h, e = _dims(cfg)
    plan = lower.lower(cfg, "decode", n, fuse_q=True, fuse_scores=True,
                       fuse_block=True, bucket=n)
    disp = lower.dispatch(plan, backend=jax_backend, interpret=interpret,
                          entry="decode_block",
                          rope=bool(cfg.rope_theta),
                          qk_norm=cfg.qk_norm, lengths_masked=True)
    x, wq, k, v, _ = _inputs(cfg, "decode", n)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    wo = jax.random.normal(ks[0], (hq, d_h, e), jnp.float32) \
        / (hq * d_h) ** 0.5
    res = jax.random.normal(ks[1], x.shape, jnp.float32)
    lens = jnp.full((x.shape[0],), n, jnp.int32)
    theta = float(cfg.rope_theta) if cfg.rope_theta else None

    if disp.path == lower.DECODE_MEGAKERNEL:
        def fn(x, wq, k, v):
            return ops.decode_block(x, wq, k, v, wo, res, lens,
                                    rope_theta=theta, plan=disp,
                                    interpret=disp.interpret)
    else:   # rung down (qk-norm): time the path that actually runs
        def fn(x, wq, k, v):
            q = jnp.einsum("bse,ehd->bhsd", x, wq)
            if theta is not None:
                from repro.kernels import ref as _ref
                q = _ref.rope(q, _ref.rope_positions(1, n, lengths=lens),
                              theta)
            o = ops.attention(q, k, v, causal=False, lengths=lens,
                              plan=disp, interpret=disp.interpret)
            return res + jnp.einsum(
                "bhse,hed->bsd", o.astype(jnp.float32),
                wo.astype(jnp.float32)).astype(x.dtype)

    def composed(x, wq, k, v):
        q = jnp.einsum("bse,ehd->bhsd", x, wq)
        if theta is not None:
            from repro.kernels import ref as _ref
            q = _ref.rope(q, _ref.rope_positions(1, n, lengths=lens),
                          theta)
        o = ops.attention(q, k, v, causal=False, lengths=lens,
                          impl="reference")
        return res + jnp.einsum(
            "bhse,hed->bsd", o.astype(jnp.float32),
            wo.astype(jnp.float32)).astype(x.dtype)

    us = _measure_us(fn, (x, wq, k, v), repeats)
    us_composed = _measure_us(composed, (x, wq, k, v), repeats)
    pred = plan.predict()
    return {
        "name": f"{arch}_decode{n}_megakernel",
        "kind": "run", "arch": arch, "phase": "decode", "n": n,
        "schedule": "megakernel", "policy": plan.block(0).policy,
        "path": disp.path, "impl": disp.impl,
        "predicted_cycles": round(pred.latency_cycles),
        "predicted_peak_words": pred.peak_active_words,
        "measured_us": round(us, 1),
        "measured_us_composed": round(us_composed, 1),
        "downgrades": [f"{g.from_path}->{g.to_path}: {g.reason}"
                       for g in plan.downgrades],
        "lengths_downgrades": sum(
            g.count for g in plan.downgrades
            if "masked-lengths" in g.reason),
    }


def _measure_us(fn, args, repeats: int) -> float:
    jfn = jax.jit(fn)
    jax.block_until_ready(jfn(*args))          # compile + warm
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _concordance(pairs) -> tuple[float, int]:
    """Fraction of candidate pairs whose predicted order matches the
    measured order; predicted near-ties (<1% apart) are skipped —
    the paper searches fused schedules at the *same* latency, so exact
    ties carry no ranking information."""
    agree = total = 0
    for i in range(len(pairs)):
        for j in range(i + 1, len(pairs)):
            (p1, m1), (p2, m2) = pairs[i], pairs[j]
            if abs(p1 - p2) <= 0.01 * max(p1, p2):
                continue
            total += 1
            if (p1 < p2) == (m1 < m2):
                agree += 1
    return (agree / total if total else 1.0), total


def validate(archs=("qwen3-8b", "starcoder2-7b"), *, smoke: bool = True,
             backend: str = "auto", prefill_seqs=(128, 512),
             decode_ctxs=(48, 512), repeats: int = 3) -> list:
    """Run the harness; returns the table as a list of dict rows
    (kind = "run" per executed plan, "ranking" per cell summary,
    "scaling" per schedule-across-shapes summary)."""
    if backend == "auto":
        backend = "native" if jax.default_backend() == "tpu" \
            else "interpret"
    interpret = backend == "interpret"
    jax_backend = jax.default_backend() if backend == "native" else \
        ("tpu" if interpret else "cpu")
    rows: list = []
    for arch in archs:
        cfg = configs.get_config(arch, smoke=smoke)
        if not lower.supported(cfg):
            rows.append({"name": f"skip_{arch}", "kind": "skip",
                         "reason": "not lowerable (MLA/SSM)"})
            continue
        for phase, shapes in (("prefill", prefill_seqs),
                              ("decode", decode_ctxs)):
            by_schedule: dict = {}
            for n in shapes:
                cell: list = []
                for label, fq, fs in CANDIDATES[phase]:
                    plan = lower.lower(cfg, phase, n, fuse_q=fq,
                                       fuse_scores=fs, bucket=n)
                    d = lower.dispatch(
                        plan, backend=jax_backend, interpret=interpret,
                        entry="qproj_attention"
                        if plan.kernel_path in (lower.QPROJ_ATTENTION,
                                                lower.DECODE_MEGAKERNEL)
                        else "attention")
                    x, wq, k, v, q_off = _inputs(cfg, phase, n)
                    fn = _candidate_fn(d, causal=True, q_offset=q_off)
                    us = _measure_us(fn, (x, wq, k, v), repeats)
                    pred = plan.predict()
                    row = {
                        "name": f"{arch}_{phase}{n}_{label}",
                        "kind": "run", "arch": arch, "phase": phase,
                        "n": n, "schedule": label,
                        "policy": plan.block(0).policy,
                        "path": d.path, "impl": d.impl,
                        "predicted_cycles": round(pred.latency_cycles),
                        "predicted_peak_words": pred.peak_active_words,
                        "measured_us": round(us, 1),
                        "downgrades": [f"{g.from_path}->{g.to_path}: "
                                       f"{g.reason}"
                                       for g in plan.downgrades],
                    }
                    rows.append(row)
                    cell.append(row)
                    by_schedule.setdefault(label, []).append(row)
                if phase == "decode":
                    rows.append(_masked_cell(
                        cfg, arch, n, jax_backend, interpret, repeats))
                    rows.append(_megakernel_cell(
                        cfg, arch, n, jax_backend, interpret, repeats))
                frac, pairs = _concordance(
                    [(r["predicted_cycles"], r["measured_us"])
                     for r in cell])
                rows.append({
                    "name": f"{arch}_{phase}{n}_ranking",
                    "kind": "ranking", "arch": arch, "phase": phase,
                    "n": n, "rank_agreement": round(frac, 3),
                    "pairs": pairs})
            for label, runs in by_schedule.items():
                if len(runs) < 2:
                    continue
                frac, pairs = _concordance(
                    [(r["predicted_cycles"], r["measured_us"])
                     for r in runs])
                rows.append({
                    "name": f"{arch}_{phase}_{label}_scaling",
                    "kind": "scaling", "arch": arch, "phase": phase,
                    "schedule": label,
                    "rank_agreement": round(frac, 3), "pairs": pairs})
    return rows


def _print_table(rows) -> None:
    runs = [r for r in rows if r["kind"] == "run"]
    if runs:
        hdr = (f"{'cell':34} {'schedule':12} {'path':16} {'impl':10} "
               f"{'pred Mcycles':>12} {'pred peak':>10} {'meas us':>10}")
        print(hdr)
        print("-" * len(hdr))
        for r in runs:
            print(f"{r['arch'] + ' ' + r['phase'] + str(r['n']):34} "
                  f"{r['schedule']:12} {r['path']:16} {r['impl']:10} "
                  f"{r['predicted_cycles'] / 1e6:12.4f} "
                  f"{r['predicted_peak_words']:10d} "
                  f"{r['measured_us']:10.1f}")
            for g in r["downgrades"]:
                print(f"{'':34} ! {g}")
        masked = [r for r in runs if "lengths_downgrades" in r]
        if masked:
            total = sum(r["lengths_downgrades"] for r in masked)
            print(f"masked-decode (dse+lengths) cells: {len(masked)}, "
                  f"lengths downgrades: {total} "
                  f"{'(planned path executed)' if total == 0 else ''}")
        print()
    for kind, title in (("ranking", "schedule-ranking agreement "
                         "(predicted-faster is measured-faster)"),
                        ("scaling", "shape-scaling agreement")):
        sel = [r for r in rows if r["kind"] == kind]
        if sel:
            print(title + ":")
            for r in sel:
                who = r.get("schedule", f"{r.get('n', '')}")
                print(f"  {r['arch']:16} {r['phase']:8} {who!s:12} "
                      f"agreement={r['rank_agreement']:.3f} "
                      f"over {r['pairs']} pairs")
            print()


def validate_memory(archs=("qwen3-8b", "starcoder2-7b"), *,
                    smoke: bool = True) -> list:
    """Measured-vs-predicted KV *memory* cells: serve a request stream
    on the paged engine and, after every decode step, compare the
    plan's page prediction over the live rows' contexts (each row owns
    exactly ``ceil(ctx / page)`` pages) with the allocator's actual
    pool occupancy.  The stream is sized to trigger at least admission
    queueing — and, pool permitting, preemption — so the agreement
    covers pages leaving and re-entering the pool, not just monotone
    growth."""
    import numpy as np

    from repro.models import init_params_and_axes
    from repro.serve import (PagedContinuousBatchingEngine, Request,
                             RequestBatcher, make_serving_plan)

    max_len, batch, page, num_pages = 96, 4, 8, 13   # 12 usable pages
    n_requests, budget = 6, 6
    rows: list = []
    for arch in archs:
        cfg = configs.get_config(arch, smoke=smoke)
        if not lower.supported(cfg):
            rows.append({"name": f"skip_{arch}", "kind": "skip",
                         "reason": "not lowerable (MLA/SSM)"})
            continue
        lower.clear_plan_cache()
        params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
        plan = make_serving_plan(cfg, max_len, paged=True,
                                 page_size=page)
        eng = PagedContinuousBatchingEngine(
            params, cfg, batch_size=batch, max_len=max_len,
            page_size=page, num_pages=num_pages, plan=plan,
            prefill_chunk=16)
        bat = RequestBatcher(batch_size=batch, eos_id=-1,
                             max_len=max_len)
        rng = np.random.default_rng(2)
        for uid in range(n_requests):
            bat.submit(Request(
                uid=uid,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(8, 41))
                                    ).tolist(),
                max_new_tokens=budget))

        exe = lower.resolve_plan(cfg, "decode", max_len,
                                 n_blocks=cfg.n_layers)
        samples, agree = 0, 0
        pred_peak = meas_peak = 0
        preempts = [0]
        orig_step, orig_pre = eng.step, eng.preempt
        eng.preempt = lambda s: (preempts.__setitem__(
            0, preempts[0] + 1), orig_pre(s))[1]

        def step():
            nonlocal samples, agree, pred_peak, meas_peak
            out = orig_step()
            lens = [eng.row_ctx[i] for i in range(batch)
                    if eng.live[i]]
            pred = exe.predicted_kv_pages(lens, page)
            # plus the page reservations of leases still mid-prefill:
            # admission reserves ceil((prompt+1)/page) pages up front
            pred += sum(
                eng.allocator.pages_for(p["tokens"].shape[1] + 1)
                for p in eng._pending.values())
            meas = eng.allocator.used_pages
            samples += 1
            agree += pred == meas
            pred_peak = max(pred_peak, pred)
            meas_peak = max(meas_peak, meas)
            return out

        eng.step = step
        done = bat.serve(eng, max_steps=400)
        w = (cfg.kv_heads, cfg.head_dim, cfg.n_layers)
        rows.append({
            "name": f"{arch}_paged_memory", "kind": "memory",
            "arch": arch, "page_size": page,
            "pool_pages": num_pages - 1, "batch": batch,
            "requests": n_requests, "completed": len(done),
            "steps": samples, "page_agreement": agree / max(samples, 1),
            "predicted_peak_pages": pred_peak,
            "measured_peak_pages": meas_peak,
            "allocator_peak_pages": eng.allocator.peak_used,
            "predicted_peak_kv_words": exe.predicted_kv_page_words(
                [pred_peak * page], page, *w),
            "measured_peak_kv_words":
                meas_peak * page * 2 * w[0] * w[1] * w[2],
            "dense_kv_words":
                batch * max_len * 2 * w[0] * w[1] * w[2],
            "preemptions": preempts[0],
        })
    return rows


def _print_memory_table(rows) -> None:
    cells = [r for r in rows if r.get("kind") == "memory"]
    if not cells:
        return
    hdr = (f"{'cell':30} {'pg agree':>8} {'pred pk':>8} {'meas pk':>8} "
           f"{'pred KV words':>13} {'meas KV words':>13} "
           f"{'dense words':>11} {'preempt':>7}")
    print("paged-KV memory validation (predicted pages per live row "
          "vs PageAllocator occupancy, per decode step):")
    print(hdr)
    print("-" * len(hdr))
    for r in cells:
        print(f"{r['name']:30} {r['page_agreement']:8.3f} "
              f"{r['predicted_peak_pages']:8d} "
              f"{r['measured_peak_pages']:8d} "
              f"{r['predicted_peak_kv_words']:13d} "
              f"{r['measured_peak_kv_words']:13d} "
              f"{r['dense_kv_words']:11d} {r['preemptions']:7d}")
    for r in rows:
        if r.get("kind") == "skip":
            print(f"  skip {r['name']}: {r['reason']}")
    print()


def validate_mesh(repeats: int = 5) -> list:
    """--mesh cells: predicted ``comm_cycles`` of head-partitioned
    multi-core schedules vs the *measured* wall-time of the collective
    the mesh lowering actually executes (one psum of per-shard output
    partials over the model axis — ``serve.distributed_decode.
    head_parallel_decode_attention``'s only cross-device traffic).

    Three (M, d_model) sizes under the round-robin allocation give the
    size-scaling ranking cells; a skewed allocation on the largest size
    is reported predicted-only — the even mesh executes the same
    balanced collective regardless of DSE-side skew, so pretending to
    "measure" it would be dishonest.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import accelerator as acc
    from repro.core import allocation as galloc
    from repro.core import scheduler as sch
    from repro.launch.mesh_lowering import mesh_for_cores

    accel = acc.multi_core_array(2)
    mesh = mesh_for_cores(2)
    n_heads = 4
    rr = (0, 1, 0, 1)
    cells = [(32, 128), (64, 256), (128, 512)]
    rows: list = []

    def predicted_comm_s(M, E, allocation):
        workload, schedule = galloc.head_partition_schedule(
            M, E, n_heads, E // n_heads, allocation)
        res = sch.evaluate(workload, accel, schedule,
                           row_block=max(1, M // 64))
        return res.comm_cycles, res.comm_cycles / accel.frequency_hz

    for M, E in cells:
        cycles, pred_s = predicted_comm_s(M, E, rr)

        def partial_sum(x):
            return jax.lax.psum(x, "model")

        fn = jax.shard_map(partial_sum, mesh=mesh,
                           in_specs=P("model", None, None),
                           out_specs=P(None, None, None), check_vma=False)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, M, E),
                              jnp.float32)
        us = _measure_us(fn, (x,), repeats)
        rows.append({
            "name": f"mesh_rr_M{M}_E{E}", "kind": "mesh",
            "allocation": rr, "M": M, "d_model": E,
            "predicted_comm_cycles": round(cycles),
            "predicted_comm_us": round(pred_s * 1e6, 4),
            "measured_collective_us": round(us, 1),
        })

    M, E = cells[-1]
    cycles, pred_s = predicted_comm_s(M, E, (0, 0, 0, 1))
    rows.append({
        "name": f"mesh_skew_M{M}_E{E}", "kind": "mesh_predicted_only",
        "allocation": (0, 0, 0, 1), "M": M, "d_model": E,
        "predicted_comm_cycles": round(cycles),
        "predicted_comm_us": round(pred_s * 1e6, 4),
        "note": "even mesh runs the same balanced psum regardless of "
                "DSE-side skew; no measured column",
    })
    frac, pairs = _concordance(
        [(r["predicted_comm_us"], r["measured_collective_us"])
         for r in rows if r["kind"] == "mesh"])
    rows.append({"name": "mesh_ranking", "kind": "ranking",
                 "arch": "mesh", "phase": "comm",
                 "rank_agreement": round(frac, 3), "pairs": pairs})
    return rows


def _print_mesh_table(rows) -> None:
    hdr = (f"{'cell':22} {'allocation':14} {'pred comm cyc':>13} "
           f"{'pred us':>9} {'meas us':>9}")
    print("predicted comm_cycles vs measured collective wall-time "
          "(2-device host mesh, psum of per-shard output partials):")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        if r["kind"] == "mesh":
            print(f"{r['name']:22} {str(r['allocation']):14} "
                  f"{r['predicted_comm_cycles']:13d} "
                  f"{r['predicted_comm_us']:9.3f} "
                  f"{r['measured_collective_us']:9.1f}")
        elif r["kind"] == "mesh_predicted_only":
            print(f"{r['name']:22} {str(r['allocation']):14} "
                  f"{r['predicted_comm_cycles']:13d} "
                  f"{r['predicted_comm_us']:9.3f} {'—':>9}")
            print(f"  note: {r['note']}")
    for r in rows:
        if r["kind"] == "ranking":
            print(f"schedule-ranking agreement (predicted-more-comm is "
                  f"measured-slower): {r['rank_agreement']:.3f} over "
                  f"{r['pairs']} pairs")


def _mesh_main(repeats: int) -> None:
    """Run the mesh comm-validation cells in this process when it has
    two devices; on the CPU, re-exec onto a forced 2-device host.  A
    one-chip TPU host is refused: the re-exec'd child could not reach
    the chip this process already holds."""
    import os
    import subprocess
    import sys
    if jax.device_count() < 2:
        if jax.default_backend() == "tpu":
            sys.exit("--mesh needs two devices in this process; this TPU "
                     "host has one")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=2"
                            ).strip()
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh",
             f"--repeats={repeats}"],
            env=env, text=True, capture_output=True)
        sys.stdout.write(out.stdout)
        sys.stderr.write(out.stderr)
        sys.exit(out.returncode)
    _print_mesh_table(validate_mesh(repeats))


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mesh", action="store_true",
                   help="validate predicted comm_cycles of lowered "
                        "multi-core schedules against measured "
                        "collective wall-time on a 2-device host mesh "
                        "(re-execs itself with forced devices if "
                        "needed); runs only the mesh cells")
    p.add_argument("--memory", action="store_true",
                   help="validate the paged-KV memory prediction "
                        "(plan page counts vs measured PageAllocator "
                        "occupancy) and nothing else; the default run "
                        "prints the same table after the latency cells")
    p.add_argument("--arch", action="append",
                   help="architecture(s) to validate (repeatable; "
                        "default qwen3-8b + starcoder2-7b)")
    p.add_argument("--full", action="store_true",
                   help="published dims instead of smoke configs")
    p.add_argument("--backend", default="auto",
                   choices=("auto", "interpret", "native"),
                   help="interpret = Pallas interpreter (CI/CPU); "
                        "native = compiled kernels (TPU)")
    p.add_argument("--prefill-seq", type=int, action="append")
    p.add_argument("--decode-ctx", type=int, action="append")
    p.add_argument("--repeats", type=int, default=3)
    a = p.parse_args(argv)
    if a.mesh:
        _mesh_main(a.repeats)
        return
    archs = tuple(a.arch) if a.arch else ("qwen3-8b", "starcoder2-7b")
    if a.memory:
        _print_memory_table(validate_memory(archs, smoke=not a.full))
        return
    rows = validate(
        archs, smoke=not a.full, backend=a.backend,
        prefill_seqs=tuple(a.prefill_seq or (128, 512)),
        decode_ctxs=tuple(a.decode_ctx or (48, 512)),
        repeats=a.repeats)
    _print_table(rows)
    _print_memory_table(validate_memory(archs, smoke=not a.full))


if __name__ == "__main__":
    main()
