"""Whole-engine snapshot/restore through checkpoint/manager.py.

A serving crash loses three kinds of state at once: the device decode
state (caches, per-row positions, block tables), the host allocator
metadata (free list, leases), and the scheduler (queue, slot leases,
per-request progress).  :func:`snapshot_engine` serialises all of it
as ONE checkpoint — the device leaves (engine state + every in-flight
prefill's side cache + every paused request's KV snapshot) go down as
a flat leaf list via ``CheckpointManager.save``; the host metadata
rides in the manifest's JSON ``extras`` with per-section leaf counts,
so :func:`restore_engine` can reassemble everything from
``restore_flat`` without a like-structured pytree.

Snapshots are taken *between* scheduler steps, where the invariants
:func:`~repro.serve.audit.audit` checks all hold; restoring one
resumes the stream bit-identically (greedy decode is deterministic),
which the chaos suite asserts token-for-token after a simulated
mid-stream crash.
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve.batcher import Request
from repro.serve.engine import PreemptedRequest, init_decode_state

__all__ = ["snapshot_engine", "restore_engine"]


def _req_to_dict(req: Request) -> dict:
    return {"uid": int(req.uid),
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "generated": [int(t) for t in req.generated],
            "done": bool(req.done), "retries": int(req.retries),
            "failed": bool(req.failed)}


def _req_from_dict(d: dict) -> Request:
    return Request(uid=d["uid"], prompt=list(d["prompt"]),
                   max_new_tokens=d["max_new_tokens"],
                   generated=list(d["generated"]), done=d["done"],
                   retries=d.get("retries", 0),
                   failed=d.get("failed", False))


def snapshot_engine(mgr, step: int, engine, batcher, *,
                    supervisor=None, blocking: bool = True) -> None:
    """Write one crash-safe checkpoint holding the full serving state:
    engine device state, in-flight prefill caches, paused-request KV
    snapshots, allocator + scheduler host metadata, and (optionally)
    the supervisor's counters."""
    state_leaves, _ = jax.tree.flatten(engine.state)
    flat = list(state_leaves)

    pending_meta = []
    for slot in sorted(engine._pending):
        p = engine._pending[slot]
        leaves, _ = jax.tree.flatten(p["cache"])
        flat.extend(leaves)
        pending_meta.append(
            {"slot": int(slot), "pos": int(p["pos"]),
             "tokens": [int(t) for t in np.asarray(p["tokens"])[0]],
             "n_leaves": len(leaves)})

    queue_meta = []
    for req in batcher.queue:
        d = _req_to_dict(req)
        if req.paused is not None:
            leaves, _ = jax.tree.flatten(req.paused.kv)
            flat.extend(leaves)
            d["paused"] = {"n_pages": int(req.paused.n_pages),
                           "length": int(req.paused.length),
                           "last_token": int(req.paused.last_token),
                           "n_leaves": len(leaves)}
        queue_meta.append(d)

    extras = {
        "serving_snapshot": 1,
        "kind": "paged" if getattr(engine, "allocator", None)
                is not None else "dense",
        "state_leaves": len(state_leaves),
        "row_ctx": [int(c) for c in engine.row_ctx],
        "live": [bool(a) for a in engine.live],
        "pending": pending_meta,
        "queue": queue_meta,
        "slots": [_req_to_dict(r) if r is not None else None
                  for r in batcher.slots],
        "slot_lens": [int(n) for n in batcher.slot_lens],
        "finished": [_req_to_dict(r) for r in batcher.finished],
    }
    alloc = getattr(engine, "allocator", None)
    if alloc is not None:
        extras["allocator"] = {
            "free": [int(p) for p in alloc._free],
            "pages": {str(k): [int(p) for p in v]
                      for k, v in alloc.pages.items()},
            "peak_used": int(alloc.peak_used),
            "notes": list(alloc.notes)}
        extras["lease_order"] = [int(x) for x in engine.lease_order]
        extras["lease_clock"] = int(engine._lease_clock)
    if supervisor is not None:
        extras["supervisor"] = supervisor.state_dict()
        extras["failed"] = [_req_to_dict(r)
                            for r in supervisor.failed]
    mgr.save(step, flat, extras=extras, blocking=blocking)


def restore_engine(mgr, engine, batcher,
                   step: Optional[int] = None,
                   supervisor=None) -> dict:
    """Reload a :func:`snapshot_engine` checkpoint into a freshly
    constructed engine + batcher (same config/geometry as the
    snapshotted ones).  Returns the checkpoint extras."""
    leaves, extras = mgr.restore_flat(step)
    if extras.get("serving_snapshot") != 1:
        raise ValueError("checkpoint is not a serving snapshot")
    pos = 0

    def take(n):
        nonlocal pos
        out, pos = leaves[pos:pos + n], pos + n
        return [jnp.asarray(a) for a in out]

    state_def = jax.tree.structure(engine.state)
    engine.state = jax.tree.unflatten(state_def,
                                      take(extras["state_leaves"]))
    engine.row_ctx = list(extras["row_ctx"])
    engine.live = list(extras["live"])
    engine._insert_backlog = []
    engine.last_logits = None

    # in-flight prefills: side caches share the dense B=1 structure
    side = init_decode_state(engine.cfg, 1, engine.max_len,
                             engine.dtype)
    side_def = jax.tree.structure(side.cache)
    engine._pending = {}
    for pm in extras["pending"]:
        cache = jax.tree.unflatten(side_def, take(pm["n_leaves"]))
        engine._pending[pm["slot"]] = {
            "tokens": np.asarray([pm["tokens"]], np.int32),
            "pos": pm["pos"], "cache": cache}

    # batcher queue (paused KV snapshots share the cache structure)
    kv_def = jax.tree.structure(engine.state.cache)
    queue = deque()
    for d in extras["queue"]:
        req = _req_from_dict(d)
        if "paused" in d:
            pm = d["paused"]
            kv = jax.tree.unflatten(
                kv_def, [np.asarray(a)
                         for a in leaves[pos:pos + pm["n_leaves"]]])
            pos += pm["n_leaves"]
            req.paused = PreemptedRequest(
                kv=kv, n_pages=pm["n_pages"], length=pm["length"],
                last_token=pm["last_token"])
        queue.append(req)
    batcher.queue = queue
    batcher.slots = [_req_from_dict(d) if d is not None else None
                     for d in extras["slots"]]
    batcher.slot_lens = list(extras["slot_lens"])
    batcher.finished = [_req_from_dict(d)
                        for d in extras["finished"]]

    alloc = getattr(engine, "allocator", None)
    if alloc is not None:
        am = extras["allocator"]
        alloc._free = list(am["free"])
        alloc.pages = {int(k): list(v) for k, v in am["pages"].items()}
        alloc.peak_used = am["peak_used"]
        alloc.notes = list(am["notes"])
        engine.lease_order = list(extras["lease_order"])
        engine._lease_clock = extras["lease_clock"]
        # between steps the device table prefix tracks the lease list
        # exactly (snapshot.py only runs there), so the mirror is
        # simply each live row's lease length
        engine._table_pages = [
            len(alloc.pages.get(i, [])) if engine.live[i] else 0
            for i in range(engine.batch_size)]

    if supervisor is not None and "supervisor" in extras:
        supervisor.load_state_dict(extras["supervisor"])
        supervisor.failed = [_req_from_dict(d)
                             for d in extras.get("failed", [])]
    return extras
