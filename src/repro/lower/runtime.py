"""Plan-driven dispatch: turn an ExecutionPlan into the concrete
kernel arguments one forward pass needs, and re-resolve plans as the
serving context grows.

Two layers:

* :func:`dispatch` — legalise one plan for one call site: map the
  kernel path onto an ``ops`` impl string for the backend, downgrade
  paths the runtime cannot execute (Q-projection fusion under qk-norm,
  megakernel without Wo/residual at the call site — RoPE no longer
  blocks anything: the fused kernels rotate the Q tile in-register),
  and record every deviation on the plan so validation tables label
  measured numbers with the path actually run.
* :class:`ServingPlan` — the serving engine's handle: holds the
  config, resolves the prefill plan once and the decode plan per
  context *bucket* (``lower.cache``), logging each re-resolution.  The
  first decode bucket edge sits at the analytical crossover
  ``C = 2N`` (``analytical.alpha_kv``), so a generation that starts
  inside two head-widths of context visibly switches kernel path the
  step its KV cache crosses it.

Pure Python (no JAX import): callers pass the backend string
(``jax.default_backend()``) in.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.lower import cache as plan_cache
from repro.lower import lowering
from repro.lower.plan import (DECODE_MEGAKERNEL, FUSED_ATTENTION,
                              QPROJ_ATTENTION, UNFUSED, ExecutionPlan)

__all__ = ["PlanDispatch", "dispatch", "impl_for", "rung_down",
           "ServingPlan", "serving_plan"]


def impl_for(path: str, backend: str = "cpu",
             interpret: bool = False) -> str:
    """Map a kernel path onto a ``kernels.ops`` impl string.  Fused
    paths lower to Pallas on TPU (or anywhere under interpret mode)
    and to the chunked-XLA streaming fallback elsewhere; the unfused
    path is the materialising reference."""
    if path == UNFUSED:
        return "reference"
    return "pallas" if (backend == "tpu" or interpret) else "xla"


@dataclasses.dataclass
class PlanDispatch:
    """Everything one attention call site needs from the plan: the
    legalised path, the impl string, the plan-resolved tiling, and the
    back-pointer for downgrade recording."""

    plan: ExecutionPlan
    path: str                   # legalised kernel path
    impl: str                   # pallas | xla | reference
    block_q: int
    block_k: int
    interpret: bool = False
    paged: bool = False         # call site passes a KV page pool +
    #                             block tables instead of dense caches

    @property
    def fuse_q(self) -> bool:
        """The call site should hand the kernel pre-projection
        activations + Wq instead of a materialised Q."""
        return self.path in (QPROJ_ATTENTION, DECODE_MEGAKERNEL)

    @property
    def fuse_wo(self) -> bool:
        """The call site should also hand over Wo and the residual —
        the whole decode attention sub-block runs as one launch."""
        return self.path == DECODE_MEGAKERNEL

    def __repr__(self) -> str:
        return (f"<PlanDispatch {self.path}/{self.impl} "
                f"blocks=({self.block_q},{self.block_k}) of {self.plan!r}>")


def dispatch(plan: ExecutionPlan, *, backend: str = "cpu",
             interpret: bool = False, entry: str = "attention",
             rope: bool = False, qk_norm: bool = False,
             lengths_masked: bool = False,
             paged: bool = False) -> PlanDispatch:
    """Legalise ``plan`` for one call site.

    Args:
        entry:   what the call site can hand the kernel —
                 "attention" (a materialised Q: the pre-megakernel
                 model runtime), "qproj_attention" (pre-projection x
                 and Wq: Q-fusion legal), or "decode_block" (x, Wq,
                 Wo AND the residual: the decode megakernel's whole
                 sub-block).  Deeper fusion needs richer entries.
        rope / qk_norm: transformations applied between the Q
                 projection and the scores.  RoPE is *fused
                 in-kernel* (the Q tile is rotated in-register) and
                 no longer blocks anything; qk-norm still breaks
                 Q-fusion (a data-dependent normalisation the kernel
                 does not fold).
        lengths_masked: the call carries a ``lengths`` mask (decode /
                 chunked prefill over a partially-filled cache).
                 Masked decode is **legal Pallas**: the scalar-prefetch
                 masked kernels (``fused_attention_masked`` /
                 ``fused_qproj_attention_masked`` /
                 ``fused_decode_block``) mask score tiles in-kernel
                 and skip KV blocks past each row's valid prefix, so
                 fused paths keep their planned impl — a note is left
                 on the plan, never a downgrade.
        paged:   the call site stores KV as a page pool + (B, max_pages)
                 block tables (the serving engine's free-list cache).
                 On a Pallas impl this is **legal**: the paged kernel
                 variants scalar-prefetch the table and index KV
                 through it (a note, never a downgrade).  On any other
                 impl the pool must be gathered dense before the masked
                 path runs — recorded as the honest paged->masked-dense
                 downgrade (the dispatch stays ``paged`` so the call
                 site still passes its tables; ``kernels.ops`` does the
                 gather).
    """
    path = plan.kernel_path
    if path == DECODE_MEGAKERNEL:
        blocked = []
        if entry != "decode_block":
            blocked.append("Wo/residual not available at this call site")
        if qk_norm:
            blocked.append("qk-norm between projection and scores")
        if blocked:
            # fall down the ladder: Q-fusion survives when the call
            # site still hands over x/Wq and nothing but RoPE sits
            # between projection and scores
            if entry in ("qproj_attention", "decode_block") \
                    and not qk_norm:
                new = QPROJ_ATTENTION
            elif plan.block(0).fuse_scores:
                new = FUSED_ATTENTION
            else:
                new = UNFUSED
            plan.record_downgrade("; ".join(blocked), path, new)
            path = new
    if path == QPROJ_ATTENTION:
        blocked = []
        if entry not in ("qproj_attention", "decode_block"):
            blocked.append("Q already materialised at this call site")
        if qk_norm:
            blocked.append("qk-norm between projection and scores")
        if blocked:
            new = FUSED_ATTENTION if plan.block(0).fuse_scores else UNFUSED
            plan.record_downgrade("; ".join(blocked), path, new)
            path = new
    if rope and path in (QPROJ_ATTENTION, DECODE_MEGAKERNEL):
        plan.note("RoPE fused in-kernel: Q tile rotated in-register "
                  "between projection and scores")
    impl = impl_for(path, backend, interpret)
    if lengths_masked and impl == "pallas":
        plan.note("masked-lengths calls take the scalar-prefetch "
                  "masked Pallas kernels (KV blocks past each row's "
                  "valid prefix skipped)")
    if paged:
        if impl == "pallas":
            plan.note("paged KV: block-table-indirect Pallas kernels "
                      "(scalar-prefetched page table drives the KV "
                      "DMAs; skipped pages issue none)")
        else:
            plan.record_downgrade(
                f"paged KV block tables unsupported on impl "
                f"'{impl}': pool gathered to masked-dense",
                path, path)
    t = plan.tiling
    return PlanDispatch(plan=plan, path=path, impl=impl,
                        block_q=t.block_q, block_k=t.block_kv,
                        interpret=interpret, paged=paged)


#: the lowering ladder, top rung first — rung-down recovery walks it
#: path by path and ends at the chunked-XLA unfused bottom rung.
_LADDER = [DECODE_MEGAKERNEL, QPROJ_ATTENTION, FUSED_ATTENTION, UNFUSED]


def rung_down(d: PlanDispatch,
              reason: str = "kernel launch failure"
              ) -> Optional[PlanDispatch]:
    """One step down the lowering ladder from a legalised dispatch:
    ``decode_megakernel -> qproj_attention -> fused_attention ->
    unfused(reference) -> unfused(xla)``, recording the step on the
    plan's downgrade ledger.  Returns the demoted dispatch, or ``None``
    from the bottom rung (nothing lower to fall to).

    This is the supervisor's kernel-failure recovery primitive
    (serve/supervisor.py): when a launch raises, the engine retries the
    step one rung lower — same math, progressively less fused — so a
    sick fused kernel degrades service instead of killing the batch.
    """
    if d.path != UNFUSED:
        new_path = _LADDER[_LADDER.index(d.path) + 1]
        new_impl = ("reference" if new_path == UNFUSED else d.impl)
    elif d.impl != "xla":
        new_path, new_impl = d.path, "xla"
    else:
        return None
    d.plan.record_downgrade(
        f"{reason}: rung-down {d.path}/{d.impl} -> "
        f"{new_path}/{new_impl}", d.path, new_path)
    return dataclasses.replace(d, path=new_path, impl=new_impl)


@dataclasses.dataclass
class ServingPlan:
    """The serving engine's plan handle for one model.

    ``prefill_dispatch``/``decode_dispatch`` resolve through the LRU
    plan cache; ``resolutions`` logs every (phase, length, bucket,
    path) the engine acted on — the end-to-end tests assert the decode
    path switch across ``crossover_ctx`` from this log.
    """

    cfg: object
    max_len: int
    backend: str = "cpu"
    interpret: bool = False
    n_blocks: int = 1
    paged: bool = False             # KV stored as page pool + tables
    page_size: Optional[int] = None
    resolutions: list = dataclasses.field(default_factory=list)
    #: every ExecutionPlan this handle resolved, by identity
    plans: dict = dataclasses.field(default_factory=dict)

    @property
    def downgrades(self) -> list:
        """The downgrade ledger across every plan this handle resolved
        (empty when the planned paths are the paths that ran)."""
        return [d for p in self.plans.values() for d in p.downgrades]

    @property
    def head_dim(self) -> int:
        return getattr(self.cfg, "head_dim", 0) or \
            self.cfg.d_model // self.cfg.n_heads

    @property
    def crossover_ctx(self) -> int:
        """The analytical decode crossover C = 2N (alpha_kv < 1 beyond
        it): the first plan-cache bucket edge, hence the first runtime
        kernel-path switch."""
        return 2 * self.head_dim

    def _dispatch(self, phase: str, n: int,
                  decode_tokens: int = 1) -> PlanDispatch:
        plan = plan_cache.resolve_plan(self.cfg, phase, n,
                                       decode_tokens=decode_tokens,
                                       n_blocks=self.n_blocks)
        # the model runtime (models/attention.py) hands the kernel
        # whatever the deepest decode fusion needs: pre-projection
        # activations + Wq always, and Wo + the residual on M=1 decode
        # steps — so the planned ladder rung is executable end-to-end
        entry = "attention"
        if phase == "decode":
            entry = "decode_block" if decode_tokens == 1 \
                else "qproj_attention"
        d = dispatch(plan, backend=self.backend, interpret=self.interpret,
                     entry=entry,
                     rope=getattr(self.cfg, "rope_theta", 0) > 0,
                     qk_norm=getattr(self.cfg, "qk_norm", False),
                     lengths_masked=True, paged=self.paged)
        self.resolutions.append((phase, n, plan.bucket, d.path, d.impl))
        self.plans[id(plan)] = plan
        return d

    def prefill_dispatch(self, seq_len: int) -> PlanDispatch:
        return self._dispatch("prefill", seq_len)

    def decode_dispatch(self, ctx_len: int) -> PlanDispatch:
        """The plan governing one decode step whose scores span
        ``ctx_len`` columns (cache prefix + the new token)."""
        return self._dispatch("decode", min(max(ctx_len, 1),
                                            self.max_len))

    def chunk_dispatch(self, ctx_len: int, rows: int) -> PlanDispatch:
        """The plan governing one *prefill chunk*: ``rows`` new query
        rows whose scores span ``ctx_len`` columns (cache prefix +
        the chunk).  The first chunk (no prefix) is plain prefill;
        later chunks are the KV-cached regime and resolve like decode
        with ``decode_tokens = rows`` — so a long prompt crossing a
        context-bucket edge mid-prefill switches kernel path exactly
        like decode does."""
        ctx_len = min(max(ctx_len, 1), self.max_len)
        if ctx_len <= rows:                      # no cache prefix yet
            return self._dispatch("prefill", rows)
        return self._dispatch("decode", ctx_len, decode_tokens=rows)

    def bucket_of(self, ctx_len: int) -> int:
        """The decode context bucket holding ``ctx_len`` — what the
        batcher groups active slots by (slots in different buckets get
        different plans, hence possibly different kernel paths)."""
        return plan_cache.bucket_for(
            "decode", min(max(ctx_len, 1), self.max_len), self.head_dim)

    def step_dispatch(self, live_lens) -> PlanDispatch:
        """One whole-batch decode dispatch resolved from the
        *distribution* of live row contexts: the deepest live row
        picks the bucket (every shallower row is legal under a deeper
        plan), and the per-row lengths flowing into the masked kernels
        do the per-row work skipping.  ``live_lens`` is the live
        slots' host-side context lengths — dead rows excluded, so a
        draining batch never plans for an evicted row's stale depth."""
        deepest = max((int(v) for v in live_lens), default=0)
        return self.decode_dispatch(deepest + 1)

    def concrete_ctx(self, cache_len) -> int:
        """Host-side context length from a DecodeState's ``cache_len``
        (a scalar, or the continuous-batching engine's per-row (B,)
        vector — the deepest row governs the whole-batch step); under
        a trace (abstract value) fall back to the buffer capacity —
        the conservative deepest-context plan."""
        from jax.errors import (ConcretizationTypeError,
                                TracerIntegerConversionError)
        try:
            if getattr(cache_len, "ndim", 0) == 1:
                return max(int(v) for v in cache_len)
            return int(cache_len)
        except (ConcretizationTypeError, TracerIntegerConversionError):
            return self.max_len


def serving_plan(cfg, max_len: int, *, backend: str = "cpu",
                 interpret: bool = False,
                 n_blocks: Optional[int] = None,
                 paged: bool = False,
                 page_size: Optional[int] = None
                 ) -> Optional[ServingPlan]:
    """Build the ServingPlan for ``cfg``, or None when the config is
    not lowerable (MLA/SSM/hybrid blocks) — the serving engine then
    keeps its config-driven dispatch.  ``paged``/``page_size``: the
    engine stores KV as a free-list page pool + block tables; every
    dispatch is then legalised on the ``paged`` axis (Pallas impls take
    the block-table-indirect kernels, others record the honest
    paged->masked-dense downgrade)."""
    if not lowering.supported(cfg):
        return None
    if n_blocks is None:
        n_blocks = getattr(cfg, "n_layers", 1) or 1
    if paged and page_size is not None and max_len % page_size:
        raise ValueError(
            f"max_len {max_len} not a multiple of page_size {page_size}")
    return ServingPlan(cfg=cfg, max_len=max_len, backend=backend,
                       interpret=interpret, n_blocks=n_blocks,
                       paged=paged, page_size=page_size)
