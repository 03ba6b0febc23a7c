"""Schedule lowering: DSE schedules -> executable Pallas plans.

The subsystem that closes the repo's loop (ROADMAP north-star step
"cost model -> production jax_pallas system"): the Stream-class DSE
stack picks phase-aware fused schedules, this package compiles them
into an :class:`ExecutionPlan` IR the runtime can dispatch on, caches
plans per ``(config, phase, seq/ctx bucket)``, and re-resolves them as
the serving context crosses the analytical ``C = 2N`` crossover.

Pure Python (no JAX) like ``core/`` — the runtime passes backend
strings in.  See docs/lowering.md for the IR spec.
"""

from repro.lower.cache import (bucket_for, clear_plan_cache, kernel_plan,
                               plan_cache_info, resolve_plan)
from repro.lower.lowering import lower, lower_phase_plan, supported
from repro.lower.plan import (DECODE_MEGAKERNEL, FUSED_ATTENTION,
                              KERNEL_PATHS, QPROJ_ATTENTION, UNFUSED,
                              BlockPlan, Downgrade, ExecutionPlan)
from repro.lower.runtime import (MLA_DECODE, PlanDispatch, ServingPlan,
                                 dispatch, impl_for, rung_down,
                                 serving_plan)

__all__ = [
    "UNFUSED", "FUSED_ATTENTION", "QPROJ_ATTENTION",
    "DECODE_MEGAKERNEL", "MLA_DECODE", "KERNEL_PATHS",
    "BlockPlan", "Downgrade", "ExecutionPlan",
    "lower", "lower_phase_plan", "supported",
    "bucket_for", "resolve_plan", "plan_cache_info", "clear_plan_cache",
    "kernel_plan",
    "PlanDispatch", "ServingPlan", "dispatch", "impl_for", "rung_down",
    "serving_plan",
]
