"""ExecutionPlan IR: the executable form of a DSE schedule.

The DSE stack (``core/``) picks phase-aware fused schedules as
``fusion.PhasePlan`` objects — workload DAGs plus ``Stage`` lists in
the analytical machine model's vocabulary.  The runtime (``kernels/``,
``serve/``) speaks a different language: which kernel entry point to
call (`fused_attention` vs `fused_qproj_attention` vs unfused
reference ops), which (block_q, block_kv) tiling to launch it with,
and which intermediates stream through VMEM vs materialise in HBM.

The ExecutionPlan IR is the bridge: per-block, per-phase records a
dispatch site can act on without re-deriving the schedule, plus the
prediction hooks (`predict`) and the honesty ledger (`record_downgrade`,
`note`) that keep measured-vs-predicted tables truthful when the
runtime cannot execute the ideal path (e.g. qk-norm between projection
and scores makes ``qproj_attention`` illegal; RoPE does not — the fused
kernels rotate the Q tile in-register — and neither blocks the decode
megakernel, which folds the Q norm too).

Pure Python — importable without JAX, like all of ``core/``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core import codesign
from repro.core import scheduler as sch

__all__ = [
    "UNFUSED", "FUSED_ATTENTION", "QPROJ_ATTENTION",
    "DECODE_MEGAKERNEL", "KERNEL_PATHS",
    "BlockPlan", "Downgrade", "ExecutionPlan",
]

#: Scores materialised, Q materialised — the LBL reference path
#: (``kernels/ref.py``).  Chosen when fusion has no predicted gain
#: (prefill M <= N, decode C <= 2N).
UNFUSED = "unfused"

#: Fig. 5c: QK^T -> softmax -> .V streamed (scores never stored).
#: Pallas ``fused_attention`` on TPU/interpret, ``xla_fallback.
#: chunked_attention`` elsewhere.
FUSED_ATTENTION = "fused_attention"

#: Fig. 5b taken all the way (the paper's ``fuse_all`` caption
#: variant): Q = x @ Wq folded into the score kernel AND the score
#: pipeline streamed.  Pallas ``fused_qproj_attention``.
QPROJ_ATTENTION = "qproj_attention"

#: The fusion ladder's M=1 decode endpoint: Q projection (+ in-kernel
#: RoPE), scores, softmax, P.V, output projection and residual add in
#: ONE Pallas launch (``kernels/fused_decode_block.py``) — zero
#: intermediate HBM round-trips for the whole attention sub-block.
DECODE_MEGAKERNEL = "decode_megakernel"

KERNEL_PATHS = (UNFUSED, FUSED_ATTENTION, QPROJ_ATTENTION,
                DECODE_MEGAKERNEL)

#: Generic per-head layer names the stream/materialise record uses
#: (the ``workload.attention_head`` vocabulary, minus prefixes).
_HEAD_CHAIN = ("Q", "QKT", "SM", "AV")


def kernel_path_for(fuse_q: bool, fuse_scores: bool,
                    fuse_block: bool = False) -> str:
    """Map the DSE's per-head fusion flags onto a runtime kernel path.

    (fuse_q, fuse_scores) -> path:
      * (False, False): ``unfused`` — the LBL reference path.
      * (True,  False): ``unfused`` too — no runtime kernel fuses the
        Q projection but still materialises scores; the flag is kept
        on the BlockPlan so the gap is visible.
      * (False, True):  ``fused_attention`` (Fig. 5c).
      * (True,  True):  ``qproj_attention`` (Fig. 5b / fuse_all).
    ``fuse_block`` (which implies both flags) escalates to
    ``decode_megakernel``.
    """
    if fuse_block:
        return DECODE_MEGAKERNEL
    if fuse_scores:
        return QPROJ_ATTENTION if fuse_q else FUSED_ATTENTION
    return UNFUSED


def _streaming(fuse_q: bool, fuse_scores: bool, fuse_block: bool = False
               ) -> tuple[tuple[tuple[str, str], ...], tuple[str, ...]]:
    """(streamed edges, materialised intermediates) per head."""
    streamed: list[tuple[str, str]] = []
    if fuse_q or fuse_block:
        streamed.append(("Q", "QKT"))
    if fuse_scores or fuse_block:
        streamed.extend([("QKT", "SM"), ("SM", "AV")])
    if fuse_block:
        # the megakernel also streams the head output through the
        # output projection and the residual add ("OUT" = resid + y@Wo)
        streamed.extend([("AV", "PROJ"), ("PROJ", "OUT")])
    producers = {a for a, _ in streamed}
    materialized = tuple(n for n in _HEAD_CHAIN[:-1] if n not in producers)
    return tuple(streamed), materialized


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Executable record for one transformer block in one phase.

    ``kernel_path`` is the DSE-ideal path (``kernel_path_for``);
    runtime legalisation (RoPE/qk-norm, masked lengths, backend) is
    applied at dispatch time (``lower.runtime.dispatch``) and logged on
    the owning :class:`ExecutionPlan`, never silently.
    """

    block_index: int
    phase: str                          # "prefill" | "decode"
    policy: str                         # lbl|fuse_q_qkt|fuse_pv|
    #                                     fuse_all|megakernel
    kernel_path: str                    # one of KERNEL_PATHS
    fuse_q: bool
    fuse_scores: bool
    tiling: codesign.AttentionTiling    # plan-resolved (block_q, block_kv)
    streamed: tuple[tuple[str, str], ...]
    materialized: tuple[str, ...]       # intermediates that hit memory
    fuse_block: bool = False            # decode megakernel

    @classmethod
    def build(cls, block_index: int, phase: str, policy: str,
              fuse_q: bool, fuse_scores: bool,
              tiling: codesign.AttentionTiling,
              fuse_block: bool = False) -> "BlockPlan":
        streamed, materialized = _streaming(fuse_q, fuse_scores,
                                            fuse_block)
        return cls(block_index=block_index, phase=phase, policy=policy,
                   kernel_path=kernel_path_for(fuse_q, fuse_scores,
                                               fuse_block),
                   fuse_q=fuse_q, fuse_scores=fuse_scores, tiling=tiling,
                   streamed=streamed, materialized=materialized,
                   fuse_block=fuse_block)


@dataclasses.dataclass
class Downgrade:
    """One (deduplicated) runtime deviation from the planned path."""

    reason: str
    from_path: str
    to_path: str
    count: int = 1


@dataclasses.dataclass
class ExecutionPlan:
    """A compiled, executable schedule for one (config, phase, bucket).

    Produced by ``lower.lowering.lower_phase_plan`` and cached by
    ``lower.cache`` keyed on ``(config, phase, seq/ctx bucket)``; the
    serving layer re-resolves it whenever the KV context crosses a
    bucket edge — the first edge sits exactly at the analytical
    crossover ``C = 2N`` (``analytical.alpha_kv``), so the kernel path
    switches at runtime where the cost model says it should.
    """

    config_name: str
    phase: str                      # "prefill" | "decode"
    M: int                          # query rows per block
    score_cols: int                 # score-matrix width C (bucketed)
    head_dim: int                   # N
    n_blocks: int
    bucket: int                     # the seq/ctx bucket resolved for
    alpha: float                    # predicted A_fused / A_LBL
    crossover_ctx: int              # 2N: decode kernel-path switch
    blocks: tuple[BlockPlan, ...]
    source: object                  # the fusion.PhasePlan lowered from
    downgrades: list[Downgrade] = dataclasses.field(default_factory=list)
    notes: list[str] = dataclasses.field(default_factory=list)
    _predicted: Optional[sch.Result] = dataclasses.field(
        default=None, repr=False, compare=False)

    # -- structure ----------------------------------------------------

    def block(self, i: int = 0) -> BlockPlan:
        return self.blocks[i]

    @property
    def kernel_path(self) -> str:
        """The (homogeneous) per-block kernel path — identical blocks
        get identical decisions, asserted at lowering time."""
        return self.blocks[0].kernel_path

    @property
    def tiling(self) -> codesign.AttentionTiling:
        return self.blocks[0].tiling

    # -- honesty ledger ----------------------------------------------

    def record_downgrade(self, reason: str, from_path: str,
                         to_path: str) -> None:
        """Record (deduplicated) that the runtime executed ``to_path``
        where the plan said ``from_path`` — validation tables must
        label measured numbers with the path actually run."""
        for d in self.downgrades:
            if (d.reason, d.from_path, d.to_path) == \
                    (reason, from_path, to_path):
                d.count += 1
                return
        self.downgrades.append(Downgrade(reason, from_path, to_path))

    def note(self, msg: str) -> None:
        if msg not in self.notes:
            self.notes.append(msg)

    @property
    def executed_path(self) -> str:
        """The path the runtime last actually took (plan path unless a
        downgrade was recorded)."""
        if self.downgrades:
            return self.downgrades[-1].to_path
        return self.kernel_path

    # -- prediction hook ---------------------------------------------

    def predict(self, accel=None, row_block: Optional[int] = None
                ) -> sch.Result:
        """Engine-evaluate the source schedule: the predicted
        cycles/peak the validation harness compares measured numbers
        against.  Only the default-platform call is memoized; an
        explicit ``accel``/``row_block`` always evaluates fresh (a
        cached default result must never masquerade as another
        platform's prediction)."""
        if accel is not None or row_block is not None:
            return self.source.evaluate(accel, row_block=row_block)
        if self._predicted is None:
            self._predicted = self.source.evaluate()
        return self._predicted

    @property
    def predicted_cycles(self) -> float:
        return self.predict().latency_cycles

    @property
    def predicted_peak_words(self) -> int:
        return self.predict().peak_active_words

    def predicted_kv_pages(self, row_lens, page_size: int) -> int:
        """Predicted peak KV *pages* for rows at contexts ``row_lens``
        under a paged cache with ``page_size``-token pages: each live
        row owns ``ceil(len / page_size)`` pages and nothing else — the
        checkable form of the cost model's memory claim (a dense cache
        would hold ``max_len`` tokens per row regardless of ``len``).
        The serving engine's allocator stats are compared against this
        by ``tools/validate_costmodel.py --memory``."""
        return sum(-(-int(l) // page_size)
                   for l in row_lens if int(l) > 0)

    def predicted_kv_page_words(self, row_lens, page_size: int,
                                n_kv_heads: int, head_dim: int,
                                n_layers: int = 1) -> int:
        """The page prediction in words: K and V planes of every
        allocated page across ``n_layers`` layers."""
        pages = self.predicted_kv_pages(row_lens, page_size)
        return pages * page_size * 2 * n_kv_heads * head_dim * n_layers

    def block_skip_fraction(self, row_lens) -> float:
        """Predicted fraction of per-row KV block iterations the
        masked kernels skip for one decode step over rows at contexts
        ``row_lens``, relative to the uniform whole-batch step (every
        row paying the deepest row's depth).  This is the per-slot
        compute saving continuous batching unlocks: each row touches
        ``ceil(len/block_kv)`` KV tiles instead of the batch maximum —
        the serving benchmark reports it next to the measured
        speedup."""
        bk = self.tiling.block_kv
        lens = [int(l) for l in row_lens if int(l) > 0]
        if not lens:
            return 0.0
        per_row = [-(-l // bk) for l in lens]
        deepest = max(per_row)
        return 1.0 - sum(per_row) / (deepest * len(per_row))

    # -- rendering ----------------------------------------------------

    def __repr__(self) -> str:
        down = f", downgrades={len(self.downgrades)}" \
            if self.downgrades else ""
        return (f"<ExecutionPlan {self.config_name} {self.phase} "
                f"M={self.M} C={self.score_cols} N={self.head_dim} "
                f"bucket={self.bucket} path={self.kernel_path} "
                f"x{self.n_blocks} blocks{down}>")

    def describe(self) -> str:
        """Human-readable plan dump (one line per block, downgrades and
        notes appended) — what `tools/validate_costmodel.py` prints."""
        head = (f"ExecutionPlan[{self.config_name} {self.phase} "
                f"M={self.M} C={self.score_cols} N={self.head_dim} "
                f"bucket={self.bucket} alpha={self.alpha:.3f} "
                f"crossover_ctx={self.crossover_ctx}]")
        lines = [head]
        for b in self.blocks:
            streamed = ",".join(f"{a}->{c}" for a, c in b.streamed) or "-"
            lines.append(
                f"  block {b.block_index}: policy={b.policy} "
                f"path={b.kernel_path} tiling=({b.tiling.block_q},"
                f"{b.tiling.block_kv}) streamed={streamed} "
                f"materialized={','.join(b.materialized) or '-'}")
        for d in self.downgrades:
            lines.append(f"  downgrade: {d.from_path} -> {d.to_path} "
                         f"x{d.count} ({d.reason})")
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)
