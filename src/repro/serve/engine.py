"""Serving: prefill + decode with per-layer caches, plan-aware.

Decode is the paper's M<N regime (one query row vs wide embeddings);
with a KV cache the analytical crossover moves to C = 2N
(``analytical.alpha_kv``): beyond two head-widths of context the
score pipeline should stream, below it materialising is free.  The
serving engine exercises that decision at runtime: pass a
``lower.runtime.ServingPlan`` and every ``prefill``/``decode_step``
resolves the ExecutionPlan governing the current context (LRU-cached
per ``(config, phase, ctx bucket)``), re-resolving — and switching
kernel path — when the KV context crosses a bucket edge; the first
edge is the crossover itself.  Without a plan the config-driven
dispatch is unchanged.

Past the crossover, M=1 decode climbs the whole fusion ladder:
``decode_megakernel`` (Q projection + in-kernel RoPE, scores, softmax,
P.V, output projection and the residual add in one Pallas launch) for
RoPE-only configs, ``qproj_attention`` when the step has multiple rows
(chunked prefill), ``fused_attention`` when qk-norm keeps Q-fusion
illegal — the downgrade recorded on the plan, never silent.

Every KV-cached step (decode and each chunked-prefill chunk) carries a
``lengths`` mask and stays on the planned Pallas path: the masked
scalar-prefetch kernels mask score tiles in-kernel, so the resolved
kernel path is the path that executes (zero lengths downgrades).

Continuous batching: ``DecodeState.cache_len`` is a (B,) int32 vector
of per-row write positions, so one whole-batch decode launch serves
rows at *different* depths — each row appends at its own position and
its own length flows into the masked kernels, which skip the KV blocks
past it (per-row compute, not just a per-row mask).  The lifecycle is
``init_decode_state → prefill_request → insert(result, slot) →
generate``: a new request is prefilled on the side (one-shot or
chunk-by-chunk, interleaved with decode steps) and its B=1 cache is
scattered into a free batch row without stopping the decode loop.
:class:`ContinuousBatchingEngine` packages the lifecycle with host
mirrors of per-slot state so step dispatch never reads device memory.

Caches: GQA k/v ring, MLA latent (B,S,576), Mamba conv+state.

Paged KV: :class:`PagedContinuousBatchingEngine` swaps the dense
per-row cache for page *pools* — per-layer ``(num_pages, Hkv, page,
Dh)`` buffers plus one ``(B, max_pages)`` int32 block table shared by
every layer — managed by a host-side :class:`PageAllocator` (free
list; page 0 is a reserved null page that dead rows harmlessly
reference).  KV memory is then bounded by the *pool*, not by
``batch * max_len``: rows only hold the pages their actual depth
needs.  Admission *reserves* the prompt's pages (plus the first
decoded token's) at lease time — a chunked prefill spans several
scheduler steps while live rows keep growing, so without the
reservation the admission check would not be binding and a finished
prefill could find the pool drained at insert.  Past that, a page is
allocated the step a row's context crosses a page boundary, and the
whole list is freed on evict.  Preemption falls out:
``preempt(slot)`` snapshots the row's pages + position to host memory
and frees them; ``resume`` scatters the snapshot into fresh pages and
the request continues bit-identically — no recompute.

``serve_step`` is what the dry-run lowers for decode_* shapes: one new
token against a seq_len-deep cache.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf
from repro.models.common import ModelConfig


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    cache: Any
    cache_len: jax.Array          # (B,) int32: per-row filled prefix
    last_token: jax.Array         # (B,) int32


def make_serving_plan(cfg: ModelConfig, max_len: int, *,
                      interpret: bool = False, paged: bool = False,
                      page_size: Optional[int] = None):
    """The ServingPlan for ``cfg`` (None when the config is not
    lowerable — MLA/SSM; serving then keeps config-driven dispatch).
    Resolved here so serve callers never touch jax backend strings.
    ``paged``/``page_size``: resolve the plan for paged-KV dispatch
    (the block-table axis of every bucket's PlanDispatch)."""
    from repro.lower import serving_plan
    return serving_plan(cfg, max_len, backend=jax.default_backend(),
                        interpret=interpret, paged=paged,
                        page_size=page_size)


def init_decode_state(cfg: ModelConfig, batch: int,
                      max_len: Optional[int] = None,
                      dtype=jnp.bfloat16, *, plan=None) -> DecodeState:
    """Allocate the cache state.  ``max_len`` may come from the plan
    (``plan.max_len``) so the cache geometry and the plan's context
    buckets are sized together."""
    if max_len is None:
        if plan is None:
            raise TypeError("init_decode_state: pass max_len or a plan")
        max_len = plan.max_len
    if plan is not None and max_len > plan.max_len:
        raise ValueError(
            f"cache max_len {max_len} exceeds the plan's {plan.max_len}: "
            "contexts past the last plan bucket would be unplanned")
    return DecodeState(
        cache=tf.init_model_cache(cfg, batch, max_len, dtype),
        cache_len=jnp.zeros((batch,), jnp.int32),
        last_token=jnp.zeros((batch,), jnp.int32),
    )


def greedy_sample(logits) -> jax.Array:
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def prefill(params, cfg: ModelConfig, tokens, state: DecodeState, *,
            embeds=None, plan=None,
            interpret: bool = False) -> DecodeState:
    """Run the prompt through the model, filling the caches.  With a
    ``ServingPlan``, the prompt-length prefill ExecutionPlan routes
    every block's attention kernel."""
    dispatch = None
    if plan is not None:
        rows = (tokens.shape[1] if tokens is not None else 0) + \
            (embeds.shape[1] if embeds is not None else 0)
        dispatch = plan.prefill_dispatch(rows)
    logits, new_cache = tf.forward(
        params, cfg, tokens=tokens, embeds=embeds, cache=state.cache,
        cache_len=0, interpret=interpret, plan=dispatch)
    b, s = logits.shape[0], logits.shape[1]
    return DecodeState(cache=new_cache,
                       cache_len=jnp.full((b,), s, jnp.int32),
                       last_token=greedy_sample(logits))


def chunked_prefill(params, cfg: ModelConfig, tokens,
                    state: DecodeState, *, chunk_size: int,
                    plan=None, interpret: bool = False) -> DecodeState:
    """Prefill a long prompt in ``chunk_size``-token chunks, appending
    each chunk to the KV cache — and, with a ``ServingPlan``,
    **re-resolving the ExecutionPlan per chunk** (``chunk_dispatch``):
    the first chunk is plain prefill, later chunks are the KV-cached
    regime (M = chunk rows vs C = prefix + chunk columns), so a prompt
    crossing a context-bucket edge mid-prefill switches kernel path at
    the edge exactly like decode does.  Every chunk after the first
    carries a ``lengths`` mask, i.e. runs the masked Pallas kernels on
    the Pallas path."""
    b, s = tokens.shape
    cache = state.cache
    logits = None
    for start in range(0, s, chunk_size):
        piece = tokens[:, start:start + chunk_size]
        dispatch = None
        if plan is not None:
            dispatch = plan.chunk_dispatch(start + piece.shape[1],
                                           piece.shape[1])
        logits, cache = tf.forward(
            params, cfg, tokens=piece, cache=cache, cache_len=start,
            interpret=interpret, plan=dispatch)
    return DecodeState(cache=cache,
                       cache_len=jnp.full((b,), s, jnp.int32),
                       last_token=greedy_sample(logits))


def decode_step(params, cfg: ModelConfig, state: DecodeState, *,
                plan=None, dispatch=None, active=None,
                block_tables=None, interpret: bool = False
                ) -> tuple[DecodeState, jax.Array]:
    """One token for every row (M=1: the paper's M<N schedule regime).

    With a ``ServingPlan`` the step re-resolves its ExecutionPlan for
    the context the scores will span (deepest row's cache prefix + the
    new token) — the kernel path switches the step the context crosses
    ``plan.crossover_ctx`` (= 2N, the analytical alpha_kv crossover).
    Beyond it, a RoPE-only config runs the decode megakernel: the whole
    attention sub-block (projection + RoPE through the residual add) is
    one Pallas launch per block.

    ``dispatch``: a pre-resolved PlanDispatch (e.g. from
    ``ServingPlan.step_dispatch`` over host-side row lengths) — skips
    the device read ``plan`` needs to learn the context.  ``active``:
    (B,) bool; rows where it is False keep their ``cache_len`` and
    ``last_token`` (free slots ride along in the batch without
    advancing — their lane's output is computed and discarded).
    ``block_tables``: (B, max_pages) int32 page table when ``state``
    is paged (pool-shaped cache leaves); the state dataclass is
    preserved either way.
    """
    if dispatch is None and plan is not None:
        ctx = plan.concrete_ctx(state.cache_len) + 1
        dispatch = plan.decode_dispatch(ctx)
    logits, new_cache = tf.forward(
        params, cfg, tokens=state.last_token[:, None],
        cache=state.cache, cache_len=state.cache_len,
        interpret=interpret, plan=dispatch, block_tables=block_tables)
    nxt = greedy_sample(logits)
    step = jnp.ones_like(state.cache_len)
    if active is not None:
        act = jnp.asarray(active)
        nxt = jnp.where(act, nxt, state.last_token)
        step = act.astype(state.cache_len.dtype)
    return dataclasses.replace(
        state, cache=new_cache, cache_len=state.cache_len + step,
        last_token=nxt), logits[:, -1]


def serve_step(params, cfg: ModelConfig, state: DecodeState, *,
               plan=None, interpret: bool = False) -> DecodeState:
    """The dry-run entry point: decode_step without returning logits."""
    new_state, _ = decode_step(params, cfg, state, plan=plan,
                               interpret=interpret)
    return new_state


# ---------------------------------------------------------------------------
# continuous batching: prefill_request -> insert -> generate
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PrefillResult:
    """A prefilled request, ready to insert: the B=1 cache (allocated
    at the engine's max_len so its rows scatter straight into the
    batch cache), the prompt length, and the first sampled token."""
    cache: Any
    length: jax.Array             # () int32: prompt tokens in the cache
    next_token: jax.Array         # () int32: first generated token


def prefill_request(params, cfg: ModelConfig, prompt, *,
                    max_len: Optional[int] = None, plan=None,
                    chunk_size: Optional[int] = None,
                    dtype=jnp.float32,
                    interpret: bool = False) -> PrefillResult:
    """Prefill one request on the side (B=1), without touching any
    decode batch: returns a :class:`PrefillResult` for ``insert``.
    ``max_len`` must match the target batch's cache geometry (taken
    from ``plan.max_len`` when omitted)."""
    toks = jnp.asarray(prompt, jnp.int32)
    if toks.ndim == 1:
        toks = toks[None, :]
    state = init_decode_state(cfg, 1, max_len, dtype, plan=plan)
    if chunk_size is None:
        state = prefill(params, cfg, toks, state, plan=plan,
                        interpret=interpret)
    else:
        state = chunked_prefill(params, cfg, toks, state,
                                chunk_size=chunk_size, plan=plan,
                                interpret=interpret)
    return PrefillResult(cache=state.cache, length=state.cache_len[0],
                         next_token=state.last_token[0])


def insert(state: DecodeState, result: PrefillResult,
           slot: int) -> DecodeState:
    """Scatter a prefilled request into batch row ``slot`` — cache
    rows, write position and last token — while every other row's
    state is untouched, so the decode loop never stops for admission.
    The result's cache must share the batch cache's max_len (enforced
    by the row-shape match of the scatter)."""
    def put(axis):
        def f(full, row):
            return jax.lax.dynamic_update_index_in_dim(
                full, jnp.squeeze(row, axis=axis).astype(full.dtype),
                slot, axis)
        return f
    # batch sits at axis 0 of prefix-layer caches and axis 1 of the
    # period-stacked scan caches (n_periods leads)
    cache = {
        "prefix": jax.tree.map(put(0), state.cache["prefix"],
                               result.cache["prefix"]),
        "scan": jax.tree.map(put(1), state.cache["scan"],
                             result.cache["scan"]),
    }
    return DecodeState(
        cache=cache,
        cache_len=state.cache_len.at[slot].set(
            jnp.asarray(result.length, jnp.int32)),
        last_token=state.last_token.at[slot].set(
            jnp.asarray(result.next_token, jnp.int32)))


def evict(state: DecodeState, slot: int) -> DecodeState:
    """Free batch row ``slot``: zero its write position and token.
    The KV rows themselves stay in place — the next ``insert`` into
    the slot overwrites them wholesale — so eviction is O(1)
    bookkeeping, and a freed row costs one masked (length ~0) lane in
    subsequent steps until it is re-leased."""
    return DecodeState(
        cache=state.cache,
        cache_len=state.cache_len.at[slot].set(0),
        last_token=state.last_token.at[slot].set(0))


class ContinuousBatchingEngine:
    """The ``init_decode_state → prefill → insert → generate``
    lifecycle as one object: a fixed-geometry decode batch whose rows
    are leased to requests and reclaimed as they finish, with new
    requests prefilled and inserted mid-stream.

    Host-side mirrors (``row_ctx``, ``live``) track per-slot state so
    each step's plan dispatch is resolved from the *distribution* of
    live row contexts (``ServingPlan.step_dispatch``) without reading
    device memory; the per-row ``cache_len`` then feeds the masked
    kernels, which skip each row's dead KV blocks — the per-slot
    compute split the per-bucket micro-batching could only approximate.

    With ``prefill_chunk`` set, a pending prompt advances one chunk
    per ``step()`` alongside the decode launch — chunked prefill
    interleaved with decode in the same scheduler step.
    """

    def __init__(self, params, cfg: ModelConfig, *, batch_size: int,
                 max_len: Optional[int] = None, plan=None,
                 dtype=jnp.float32, prefill_chunk: Optional[int] = None,
                 interpret: bool = False):
        if max_len is None:
            if plan is None:
                raise TypeError(
                    "ContinuousBatchingEngine: pass max_len or a plan")
            max_len = plan.max_len
        self.params, self.cfg, self.plan = params, cfg, plan
        self.batch_size, self.max_len = batch_size, max_len
        self.dtype, self.interpret = dtype, interpret
        self.prefill_chunk = prefill_chunk
        self.state = self._init_state()
        self.row_ctx = [0] * batch_size   # host mirror of cache_len
        self.live = [False] * batch_size
        self._pending: dict = {}          # slot -> in-flight prefill
        # insertions whose (slot, first_token) the caller has not yet
        # been handed — survives a raised launch mid-_advance_prefills
        # so a retried step still reports every completed insert
        self._insert_backlog: list = []
        #: standing rung-down count applied to every resolved dispatch
        #: (the supervisor's kernel-failure recovery; see
        #: lower/runtime.py:rung_down).  0 = run the planned path.
        self.demotions = 0
        #: serve-layer fault injector (serve/faults.py); None outside
        #: chaos tests.
        self.fault_injector = None
        #: one jitted launch per (kind, legalised dispatch) — see _launch
        self._launches: dict = {}
        #: host copy of the last decode launch's final-position logits
        #: (B, vocab) — the supervisor's NaN-detection window.
        self.last_logits: Optional[np.ndarray] = None
        self.last_dispatch = None

    def _init_state(self):
        return init_decode_state(self.cfg, self.batch_size, self.max_len,
                                 self.dtype, plan=self.plan)

    @property
    def occupancy(self) -> float:
        return sum(self.live) / self.batch_size

    def free_slots(self) -> list:
        return [i for i in range(self.batch_size)
                if not self.live[i] and i not in self._pending]

    def begin_prefill(self, slot: int, prompt) -> None:
        """Lease ``slot`` to a new request.  The prompt is prefilled on
        a side B=1 cache — one-shot, or (with ``prefill_chunk``) one
        chunk per subsequent ``step()`` — and inserted into the slot
        when complete; the decode loop never pauses."""
        if self.live[slot] or slot in self._pending:
            raise ValueError(f"slot {slot} is not free")
        toks = np.asarray(prompt, np.int32)[None, :]
        if toks.shape[1] > self.max_len:
            raise ValueError(f"prompt ({toks.shape[1]} tokens) exceeds "
                             f"cache max_len {self.max_len}")
        side = init_decode_state(self.cfg, 1, self.max_len, self.dtype)
        self._pending[slot] = {"tokens": toks, "pos": 0,
                               "cache": side.cache}

    def _advance_prefills(self) -> list:
        """Run one prefill chunk per pending request; insert the ones
        that complete.  Returns [(slot, first_token), ...].  Retry-safe:
        completions are staged on ``_insert_backlog``, so a launch
        failure partway through the pending set never loses an already
        -inserted request's first token."""
        inserted = self._insert_backlog
        for slot, p in list(self._pending.items()):
            total = p["tokens"].shape[1]
            chunk = self.prefill_chunk or total
            piece = p["tokens"][:, p["pos"]:p["pos"] + chunk]
            dispatch = None
            if self.plan is not None:
                dispatch = self._demoted(self.plan.chunk_dispatch(
                    p["pos"] + piece.shape[1], piece.shape[1]))
            self._check_kernel(dispatch)
            logits, p["cache"] = self._launch("prefill", dispatch)(
                self.params, jnp.asarray(piece), p["cache"],
                jnp.int32(p["pos"]))
            p["pos"] += piece.shape[1]
            if p["pos"] >= total:
                res = PrefillResult(
                    cache=p["cache"],
                    length=jnp.asarray(total, jnp.int32),
                    next_token=greedy_sample(logits)[0])
                self._insert(res, slot)
                self.row_ctx[slot] = total
                self.live[slot] = True
                del self._pending[slot]
                inserted.append((slot, int(res.next_token)))
        self._insert_backlog = []
        return inserted

    def _insert(self, res: PrefillResult, slot: int) -> None:
        self.state = insert(self.state, res, slot)

    def _before_decode(self) -> None:
        """Hook run right before each decode launch (the paged engine
        grows page lists for rows crossing a page boundary here)."""

    def _launch(self, kind: str, dispatch):
        """The jitted launch for one legalised dispatch: ``"decode"``
        (one whole-batch token step) or ``"prefill"`` (one chunk into a
        B=1 side cache).  One compiled program per (kind, path, impl,
        block sizes, paged); ``cache_len``, the chunk offset, the
        active mask and the cache are traced arguments, so a steady
        step compiles nothing.  Kernel-side work that ``kernels.ops``
        does per call (tiling, downgrade records) now runs once, when
        the program is traced."""
        key = (kind, None if dispatch is None else (
            dispatch.path, dispatch.impl, dispatch.block_q,
            dispatch.block_k, dispatch.interpret, dispatch.paged))
        fn = self._launches.get(key)
        if fn is not None:
            return fn
        cfg, interpret = self.cfg, self.interpret
        if kind == "decode":
            def fn(params, state, active):
                return decode_step(
                    params, cfg, state, dispatch=dispatch, active=active,
                    interpret=interpret,
                    block_tables=getattr(state, "block_tables", None))
        else:
            def fn(params, tokens, cache, pos):
                logits, cache = tf.forward(
                    params, cfg, tokens=tokens, cache=cache,
                    cache_len=pos, interpret=interpret, plan=dispatch)
                return logits[:, -1:], cache
        fn = self._launches[key] = jax.jit(fn)
        return fn

    def _check_kernel(self, dispatch) -> None:
        """Fault hook, consulted on the host before every launch: a
        kernel fault armed for the impl this dispatch runs raises here
        (chaos testing only — the kernels themselves carry no hook)."""
        if self.fault_injector is None:
            return
        if dispatch is None:
            self.fault_injector.on_kernel("attention", self.cfg.attn_impl)
        else:
            self.fault_injector.on_kernel(dispatch.path, dispatch.impl)

    def _demoted(self, dispatch):
        """Apply the standing ``demotions`` count to a resolved
        dispatch: each unit walks it one rung down the lowering ladder
        (kernel-failure recovery; the descent is recorded on the plan's
        downgrade ledger by ``rung_down``)."""
        if dispatch is None or not self.demotions:
            return dispatch
        from repro.lower.runtime import rung_down
        for _ in range(self.demotions):
            lower = rung_down(dispatch, "kernel-failure recovery")
            if lower is None:
                break
            dispatch = lower
        return dispatch

    def _inject_nan(self) -> None:
        """Fault hook: poison one live slot's logits/token this step if
        the installed injector says so (chaos testing only)."""
        inj = self.fault_injector
        if inj is None:
            return
        slot = inj.nan_slot()
        if slot is None or slot >= self.batch_size \
                or not self.live[slot]:
            return
        if self.last_logits is not None:
            # np.asarray of a device buffer is a read-only view
            self.last_logits = self.last_logits.copy()
            self.last_logits[slot] = np.nan
        self.state = dataclasses.replace(
            self.state,
            last_token=self.state.last_token.at[slot].set(0))

    def decode_once(self):
        """The decode half of :meth:`step`: one whole-batch launch over
        the live rows (no prefill advance).  Returns the (B,) last
        tokens, or None when no row is live.  Retry-safe: host and
        device state are only advanced after the launch succeeds, so a
        raised launch (kernel failure, ``OutOfPages`` from the in-step
        ``ensure``) leaves the step re-runnable."""
        if not any(self.live):
            self.last_logits = None
            return None
        self._before_decode()
        dispatch = None
        if self.plan is not None:
            dispatch = self._demoted(self.plan.step_dispatch(
                [c for c, alive in zip(self.row_ctx, self.live)
                 if alive]))
        self.last_dispatch = dispatch
        self._check_kernel(dispatch)
        new_state, logits = self._launch("decode", dispatch)(
            self.params, self.state, jnp.asarray(self.live))
        self.state = new_state
        self.last_logits = np.asarray(logits)
        self._inject_nan()
        for i in range(self.batch_size):
            if self.live[i]:
                self.row_ctx[i] += 1
        return np.asarray(self.state.last_token)

    def step(self):
        """One scheduler step: advance every pending prefill by one
        chunk (inserting completions), then one whole-batch decode
        launch over the live rows — per-row lengths let the masked
        kernels skip each row's dead KV blocks.  Returns
        ``(tokens, inserted)``: the (B,) last tokens (None if no row
        is live) and the [(slot, first_token), ...] insertions."""
        inserted = self._advance_prefills()
        return self.decode_once(), inserted

    # the lifecycle verb: prefill -> insert -> *generate*
    generate = step

    def rollback_slot(self, slot: int, ctx: int, token: int) -> None:
        """Rewind row ``slot`` to a known-good (context, last token) —
        the supervisor's quarantine primitive.  The rewound step's KV
        write is left beyond the restored length, where the masked
        kernels never read it (and a replay overwrites it with the
        identical values, since K/V depend only on the clean input
        token and position)."""
        self.state = dataclasses.replace(
            self.state,
            cache_len=self.state.cache_len.at[slot].set(int(ctx)),
            last_token=self.state.last_token.at[slot].set(int(token)))
        self.row_ctx[slot] = int(ctx)

    def can_resume(self, pre: "PreemptedRequest") -> bool:
        """Dense rows are pre-allocated: a snapshot can always
        re-enter a free slot (the paged engine overrides with its page
        check)."""
        return True

    def preempt(self, slot: int) -> "PreemptedRequest":
        """Snapshot row ``slot``'s cache rows + position to host memory
        and free the lane — the dense twin of the paged engine's verb,
        so the supervisor drives both engines uniformly.  (Nothing to
        give back to an allocator: dense rows are pre-allocated.)"""
        if not self.live[slot]:
            raise ValueError(f"slot {slot} is not live")

        def take(axis):
            def f(full):
                return jax.lax.dynamic_slice_in_dim(full, slot, 1, axis)
            return f
        # batch at axis 0 of prefix-layer caches, axis 1 of the
        # period-stacked scan caches — the layout ``insert`` scatters
        kv = {"prefix": jax.tree.map(take(0), self.state.cache["prefix"]),
              "scan": jax.tree.map(take(1), self.state.cache["scan"])}
        pre = PreemptedRequest(
            kv=jax.device_get(kv), n_pages=0,
            length=self.row_ctx[slot],
            last_token=int(np.asarray(self.state.last_token)[slot]))
        self.evict(slot)
        return pre

    def resume(self, pre: "PreemptedRequest", slot: int) -> None:
        """Re-admit a preempted snapshot into free slot ``slot``; the
        request continues bit-identically, no prefill recompute."""
        if self.live[slot] or slot in self._pending:
            raise ValueError(f"slot {slot} is not free")
        res = PrefillResult(
            cache=jax.tree.map(jnp.asarray, pre.kv),
            length=jnp.asarray(pre.length, jnp.int32),
            next_token=jnp.asarray(pre.last_token, jnp.int32))
        self._insert(res, slot)
        self.row_ctx[slot] = pre.length
        self.live[slot] = True

    def evict(self, slot: int) -> None:
        """Reclaim ``slot`` (request finished or cancelled): frees the
        row for the next ``begin_prefill`` without touching any other
        row's cache."""
        self.state = evict(self.state, slot)
        self.row_ctx[slot] = 0
        self.live[slot] = False


# ---------------------------------------------------------------------------
# paged KV: PageAllocator -> PagedDecodeState -> paged engine
# ---------------------------------------------------------------------------

class OutOfPages(RuntimeError):
    """The page pool cannot satisfy an allocation: the caller must
    preempt a live request (or wait for one to finish) first."""


class PageAllocator:
    """Host-side free-list allocator over a fixed KV page pool.

    Page 0 is a reserved *null page*: it is never handed out, so a
    zeroed block-table row (a dead batch lane) references it harmlessly
    — the masked kernels never read past a dead row's length 0 anyway,
    and the clamp in the paged index maps keeps even the skipped
    iterations inside the pool.  Keys are arbitrary (the engine uses
    batch slot indices); ``pages[key]`` lists the key's page ids in row
    order, i.e. exactly the prefix of its block-table row.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the reserved "
                             "null page)")
        if page_size % 8:
            raise ValueError("page_size must be sublane-aligned (8)")
        self.num_pages = num_pages
        self.page_size = page_size
        # pop() order 1, 2, 3, ... — page 0 never enters the free list
        self._free = list(range(num_pages - 1, 0, -1))
        self.pages: dict = {}             # key -> [page ids, row order]
        self.peak_used = 0
        #: bookkeeping oddities worth surfacing (e.g. a release of an
        #: already-released key) — recorded, never raised.
        self.notes: list = []
        #: serve-layer fault injector (serve/faults.py); every alloc
        #: (and thus every ensure that grows) consults it first.
        self.fault_injector = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` KV entries."""
        return -(-int(n_tokens) // self.page_size)

    def alloc(self, key, n: int) -> list:
        """Append ``n`` fresh pages to ``key``'s list.  All-or-nothing:
        raises :class:`OutOfPages` (allocating none) when the free list
        is short."""
        if self.fault_injector is not None:
            self.fault_injector.on_alloc(key, n)
        if n > len(self._free):
            raise OutOfPages(
                f"need {n} pages for {key!r} but only {len(self._free)} "
                f"of {self.num_pages - 1} are free — preempt or evict")
        ids = [self._free.pop() for _ in range(n)]
        self.pages.setdefault(key, []).extend(ids)
        self.peak_used = max(self.peak_used, self.used_pages)
        return ids

    def ensure(self, key, n_tokens: int) -> list:
        """Grow ``key``'s list to cover ``n_tokens`` entries; returns
        the newly allocated ids ([] when already covered)."""
        need = self.pages_for(n_tokens) - len(self.pages.get(key, []))
        return self.alloc(key, need) if need > 0 else []

    def release(self, key) -> list:
        """Free every page held by ``key``.  Idempotent: an unknown or
        already-released key returns ``[]`` with a recorded note — a
        double release is a scheduler bookkeeping smell worth
        surfacing, never worth killing the batch over."""
        if key not in self.pages:
            self.notes.append(
                f"release({key!r}): unknown or already-released key "
                f"(no-op)")
            return []
        ids = self.pages.pop(key)
        self._free.extend(reversed(ids))
        return ids


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedDecodeState:
    """DecodeState whose cache leaves are page pools
    ``(num_pages, Hkv, page, Dh)`` (scan layers carry the usual leading
    n_periods axis) plus the ``(B, max_pages)`` int32 block table every
    layer shares."""
    cache: Any
    cache_len: jax.Array          # (B,) int32: per-row filled prefix
    last_token: jax.Array         # (B,) int32
    block_tables: jax.Array       # (B, max_pages) int32 page ids


@dataclasses.dataclass
class PreemptedRequest:
    """A preempted request's host-side snapshot: the gathered page
    contents per layer (same {"prefix","scan"} structure as the cache,
    attn leaves shaped (n, Hkv, page, Dh) / (n_periods, n, ...)), its
    token position and last sampled token.  ``resume`` scatters the
    snapshot into freshly allocated pages — the KV bits are identical,
    so the continuation is identical."""
    kv: Any
    n_pages: int
    length: int
    last_token: int


def _check_paged_cfg(cfg: ModelConfig) -> None:
    if cfg.attention == "mla":
        raise NotImplementedError(
            "paged KV is not supported for MLA latent caches")
    for i in range(cfg.n_layers):
        if cfg.block_kind(i) != "attn":
            raise NotImplementedError(
                "paged KV pools cover GQA attention caches only "
                f"(layer {i} is {cfg.block_kind(i)!r})")


def init_paged_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                            *, num_pages: int, page_size: int,
                            dtype=jnp.bfloat16) -> PagedDecodeState:
    """Allocate the paged cache state: per-layer page pools plus one
    zeroed block table.  ``max_len`` bounds a single row's context and
    fixes the table width; the *pool* bounds total KV memory."""
    _check_paged_cfg(cfg)
    if max_len % page_size:
        raise ValueError(f"max_len {max_len} must be a multiple of the "
                         f"page size {page_size}")
    hk, dh = cfg.kv_heads, cfg.head_dim

    def pool():
        return jnp.zeros((num_pages, hk, page_size, dh), dtype)

    prefix = [{"attn": {"k": pool(), "v": pool()}}
              for _ in range(cfg.first_dense_layers)]
    scan = [jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape),
        {"attn": {"k": pool(), "v": pool()}})
        for _ in range(cfg.layer_period)]
    return PagedDecodeState(
        cache={"prefix": prefix, "scan": scan},
        cache_len=jnp.zeros((batch,), jnp.int32),
        last_token=jnp.zeros((batch,), jnp.int32),
        block_tables=jnp.zeros((batch, max_len // page_size), jnp.int32))


def _map_attn_leaves(cache, fn):
    """Apply ``fn(leaf, scanned)`` to every attn cache leaf (paged
    caches hold only attn leaves — enforced at init)."""
    def one(lc, scanned):
        return {"attn": {k: fn(v, scanned)
                         for k, v in lc["attn"].items()}}
    return {"prefix": [one(lc, False) for lc in cache["prefix"]],
            "scan": [one(lc, True) for lc in cache["scan"]]}


def _map_attn_pairs(cache, other, fn):
    """Like :func:`_map_attn_leaves` over paired trees:
    ``fn(cache_leaf, other_leaf, scanned)``."""
    def one(lc, oc, scanned):
        return {"attn": {k: fn(lc["attn"][k], oc["attn"][k], scanned)
                         for k in lc["attn"]}}
    return {"prefix": [one(a, b, False) for a, b
                       in zip(cache["prefix"], other["prefix"])],
            "scan": [one(a, b, True) for a, b
                     in zip(cache["scan"], other["scan"])]}


def _page_chunks(dense_row, n: int, page: int):
    """(Hkv, max_len, Dh) dense row -> its first n pages,
    (n, Hkv, page, Dh)."""
    hkv, _, dh = dense_row.shape
    return jnp.moveaxis(
        dense_row[:, :n * page].reshape(hkv, n, page, dh), 1, 0)


def _set_table_row(tables, slot: int, idx):
    """Zero row ``slot`` and write ``idx`` as its leading prefix."""
    row = jnp.zeros((tables.shape[1],), jnp.int32)
    row = jax.lax.dynamic_update_slice(row, idx, (0,))
    return tables.at[slot].set(row)


def insert_paged(state: PagedDecodeState, result: PrefillResult,
                 slot: int, page_ids: list) -> PagedDecodeState:
    """Scatter a *dense* B=1 prefill cache into pool pages: each
    layer's (1, Hkv, max_len, Dh) rows are cut into page chunks and
    written to ``page_ids``; the slot's block-table row becomes
    ``page_ids`` (zero-padded).  Prefill itself stays dense-side —
    paging happens once, here, at admission."""
    idx = jnp.asarray(page_ids, jnp.int32)
    n = len(page_ids)

    def put(pool, dense, scanned):
        if scanned:
            # (n_periods, num_pages, ...) vs (n_periods, 1, Hkv, S, Dh)
            return jax.vmap(lambda p, d: p.at[idx].set(
                _page_chunks(d, n, p.shape[2]).astype(p.dtype)))(
                    pool, dense[:, 0])
        return pool.at[idx].set(
            _page_chunks(dense[0], n, pool.shape[2]).astype(pool.dtype))

    return PagedDecodeState(
        cache=_map_attn_pairs(state.cache, result.cache, put),
        cache_len=state.cache_len.at[slot].set(
            jnp.asarray(result.length, jnp.int32)),
        last_token=state.last_token.at[slot].set(
            jnp.asarray(result.next_token, jnp.int32)),
        block_tables=_set_table_row(state.block_tables, slot, idx))


def evict_paged(state: PagedDecodeState, slot: int) -> PagedDecodeState:
    """Free batch row ``slot``: zero its table row, position and token.
    (The caller releases the pages on the allocator — the pool bits
    stay put and are overwritten when the pages are next handed out.)"""
    return PagedDecodeState(
        cache=state.cache,
        cache_len=state.cache_len.at[slot].set(0),
        last_token=state.last_token.at[slot].set(0),
        block_tables=state.block_tables.at[slot].set(0))


def gather_slot_pages(state: PagedDecodeState, page_ids: list):
    """The page contents backing one row, gathered from every layer's
    pool (device arrays; ``jax.device_get`` for a host snapshot)."""
    idx = jnp.asarray(page_ids, jnp.int32)
    return _map_attn_leaves(
        state.cache,
        lambda leaf, scanned: leaf[:, idx] if scanned else leaf[idx])


def resume_paged(state: PagedDecodeState, pre: PreemptedRequest,
                 slot: int, page_ids: list) -> PagedDecodeState:
    """Scatter a preempted request's KV snapshot into fresh pages and
    re-point the slot's table row at them.  The pages differ, the bits
    do not — generation continues exactly where preemption cut it."""
    idx = jnp.asarray(page_ids, jnp.int32)

    def put(pool, saved, scanned):
        saved = jnp.asarray(saved, pool.dtype)
        if scanned:
            return jax.vmap(lambda p, s: p.at[idx].set(s))(pool, saved)
        return pool.at[idx].set(saved)

    return PagedDecodeState(
        cache=_map_attn_pairs(state.cache, pre.kv, put),
        cache_len=state.cache_len.at[slot].set(pre.length),
        last_token=state.last_token.at[slot].set(pre.last_token),
        block_tables=_set_table_row(state.block_tables, slot, idx))


class PagedContinuousBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching over a paged KV cache.

    Same lifecycle and scheduler interface as the dense engine
    (``begin_prefill / step / evict`` — :class:`RequestBatcher.serve`
    drives both), but the cache is a page pool: ``begin_prefill``
    *reserves* ``ceil((len+1)/page)`` pages for the lease up front (so
    live rows growing during a chunked prefill cannot drain the pool
    out from under it), the completed prefill scatters into the
    reserved pages, each decode step then grows the page list of any
    live row crossing a page boundary, and eviction returns the pages
    to the free list.  Two new verbs:

    * ``preempt(slot)`` — snapshot the row's pages + position to host
      memory, free the pages, clear the slot.  Costs one gather.
    * ``resume(pre, slot)`` — re-admit a snapshot into fresh pages;
      the request continues bit-identically, no prefill recompute.

    ``step_page_deficit()`` tells the scheduler how many pages short
    the *next* decode step would run — its cue to preempt before the
    in-step ``ensure`` raises :class:`OutOfPages`.
    """

    def __init__(self, params, cfg: ModelConfig, *, batch_size: int,
                 page_size: int, num_pages: int,
                 max_len: Optional[int] = None, plan=None,
                 dtype=jnp.float32, prefill_chunk: Optional[int] = None,
                 interpret: bool = False):
        self.page_size, self.num_pages = page_size, num_pages
        self.allocator = PageAllocator(num_pages, page_size)
        # monotone lease stamps: the scheduler preempts the *newest*
        # lease first (it has the least sunk prefill/decode work)
        self.lease_order = [0] * batch_size
        self._lease_clock = 0
        # host mirror of how many of each slot's pages the *device*
        # block table already indexes — lets a decode step retried
        # after a mid-loop OutOfPages re-derive exactly the table
        # writes the failed attempt never committed
        self._table_pages = [0] * batch_size
        super().__init__(params, cfg, batch_size=batch_size,
                         max_len=max_len, plan=plan, dtype=dtype,
                         prefill_chunk=prefill_chunk,
                         interpret=interpret)

    def _init_state(self):
        return init_paged_decode_state(
            self.cfg, self.batch_size, self.max_len,
            num_pages=self.num_pages, page_size=self.page_size,
            dtype=self.dtype)

    # -- page accounting ---------------------------------------------------

    def can_admit_tokens(self, n_tokens: int) -> bool:
        """Can a fresh ``n_tokens``-token prompt be admitted now?  It
        needs pages for the prompt plus its first decoded token."""
        return self.allocator.pages_for(n_tokens + 1) \
            <= self.allocator.num_free

    def can_resume(self, pre: PreemptedRequest) -> bool:
        """Can a preempted snapshot be re-admitted now?  It needs its
        saved pages back, and room for the next decoded token."""
        return max(pre.n_pages,
                   self.allocator.pages_for(pre.length + 1)) \
            <= self.allocator.num_free

    def step_page_deficit(self) -> int:
        """Pages the next decode step needs beyond the free list (0
        when the step can run)."""
        need = sum(
            max(0, self.allocator.pages_for(self.row_ctx[i] + 1)
                - len(self.allocator.pages.get(i, [])))
            for i in range(self.batch_size) if self.live[i])
        return max(0, need - self.allocator.num_free)

    # -- lifecycle overrides -----------------------------------------------

    def begin_prefill(self, slot: int, prompt) -> None:
        """Lease ``slot`` AND reserve the prompt's pages (plus the
        first decoded token's — the quantity ``can_admit_tokens``
        checks).  The prefill itself runs on a dense side cache over
        the following steps; the reservation guarantees the pool can
        take the result no matter how the live rows grow meanwhile."""
        super().begin_prefill(slot, prompt)
        try:
            self.allocator.alloc(
                slot, self.allocator.pages_for(len(prompt) + 1))
        except OutOfPages:
            del self._pending[slot]
            raise

    def _insert(self, res: PrefillResult, slot: int) -> None:
        self.state = insert_paged(self.state, res, slot,
                                  self.allocator.pages[slot])
        self._table_pages[slot] = len(self.allocator.pages[slot])
        self._lease_clock += 1
        self.lease_order[slot] = self._lease_clock

    def _before_decode(self) -> None:
        # Grow rows whose next token crosses into a new page; one
        # batched table update regardless of how many rows grew.  Two
        # phases for crash safety: ``ensure`` may raise OutOfPages
        # mid-loop *after* earlier rows' allocations committed on the
        # allocator, so the device table and its host mirror are only
        # touched once every ensure has succeeded — a retry then sees
        # ``pages[i]`` ahead of ``_table_pages[i]`` and (re)issues
        # exactly the writes the failed attempt never made.
        updates = []
        for i in range(self.batch_size):
            if not self.live[i]:
                continue
            self.allocator.ensure(i, self.row_ctx[i] + 1)
            ids = self.allocator.pages.get(i, [])
            if len(ids) != self._table_pages[i]:
                updates.append((i, self._table_pages[i],
                                ids[self._table_pages[i]:]))
        if updates:
            tbl = self.state.block_tables
            for i, start, new in updates:
                tbl = jax.lax.dynamic_update_slice(
                    tbl, jnp.asarray([new], jnp.int32), (i, start))
            self.state = dataclasses.replace(self.state,
                                             block_tables=tbl)
            for i, start, new in updates:
                self._table_pages[i] = start + len(new)

    def evict(self, slot: int) -> None:
        self.allocator.release(slot)
        self.state = evict_paged(self.state, slot)
        self.row_ctx[slot] = 0
        self.live[slot] = False
        self._table_pages[slot] = 0

    def preempt(self, slot: int) -> PreemptedRequest:
        """Save row ``slot``'s KV pages + position to host memory and
        free the slot (pages, table row, lane).  The snapshot re-enters
        through :meth:`resume` without any recompute."""
        if not self.live[slot]:
            raise ValueError(f"slot {slot} is not live")
        ids = list(self.allocator.pages[slot])
        pre = PreemptedRequest(
            kv=jax.device_get(gather_slot_pages(self.state, ids)),
            n_pages=len(ids),
            length=self.row_ctx[slot],
            last_token=int(self.state.last_token[slot]))
        self.allocator.release(slot)
        self.state = evict_paged(self.state, slot)
        self.row_ctx[slot] = 0
        self.live[slot] = False
        self._table_pages[slot] = 0
        return pre

    def resume(self, pre: PreemptedRequest, slot: int) -> None:
        """Re-admit a preempted snapshot into free slot ``slot``."""
        if self.live[slot] or slot in self._pending:
            raise ValueError(f"slot {slot} is not free")
        ids = self.allocator.alloc(slot, pre.n_pages)
        self.state = resume_paged(self.state, pre, slot, ids)
        self.row_ctx[slot] = pre.length
        self.live[slot] = True
        self._table_pages[slot] = len(ids)
        self._lease_clock += 1
        self.lease_order[slot] = self._lease_clock
