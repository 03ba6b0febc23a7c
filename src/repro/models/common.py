"""Shared model components: config, parameter construction with logical
sharding axes, norms, RoPE, MLPs, embeddings, loss.

Models are pure functions over parameter pytrees (no flax dependency):
``init_*`` builds a tree whose leaves are ``Param(value, logical_axes)``;
``split_params`` separates values from the axes tree used to derive
NamedShardings for pjit.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.sharding import constrain


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling (arXiv:2309.00071) as DeepSeek-V3 publishes it
    (``rope_scaling`` of its config.json): frequencies past the
    ``beta_fast`` correction dimension are interpolated by ``factor``,
    those before ``beta_slow``'s kept, a linear ramp between; the
    attention logits are scaled by ``softmax_mscale``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _m(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 \
            else 1.0

    def correction_range(self, d: int, base: float) -> tuple[int, int]:
        """(low, high) rotary pair indices of the ramp, clamped to
        [0, d - 1]."""
        def find(r):
            return d * math.log(self.original_max_position
                                / (2 * math.pi * r)) / (2 * math.log(base))
        low = math.floor(find(self.beta_fast))
        high = math.ceil(find(self.beta_slow))
        return max(low, 0), min(high, d - 1)

    def inv_freq(self, d: int, base: float):
        """(d/2,) float32 frequencies: ``inter * ramp + extra * (1 -
        ramp)``, with extra = base^(-2i/d), inter = extra / factor."""
        half = d // 2
        i = jnp.arange(half, dtype=jnp.float32)
        extra = 1.0 / (base ** (2.0 * i / d))
        inter = extra / self.factor
        low, high = self.correction_range(d, base)
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return inter * ramp + extra * (1.0 - ramp)

    @property
    def cos_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return self._m(self.factor, self.mscale) \
            / self._m(self.factor, self.mscale_all_dim)

    @property
    def softmax_mscale(self) -> float:
        """What the attention logits' scale is multiplied by: m^2, with
        m = 0.1 ln(factor) mscale_all_dim + 1."""
        return self._m(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers all 10 assigned architectures (``configs/``)."""

    name: str
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab_size: int
    n_kv_heads: int = 0                 # 0 -> = n_heads
    d_head: int = 0                     # 0 -> d_model // n_heads
    # attention flavour
    attention: str = "gqa"              # gqa | mla | none
    qk_norm: bool = False
    causal: bool = True                 # False: encoder-only (hubert)
    rope_theta: float = 1e6
    rope_scaling: Optional[YaRN] = None  # None: plain RoPE
    # MLA (deepseek-v3)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_rope_head_dim: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE
    moe: bool = False
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 2
    d_expert: int = 0
    capacity_factor: float = 1.25
    # router: "softmax" (top-k over softmax probs, capacity drops) or
    # "sigmoid" (deepseek-v3 noaux_tc: sigmoid scores, a selection-only
    # bias, group-limited top-k, no drops)
    router: str = "softmax"
    n_groups: int = 1                   # sigmoid: expert groups
    topk_groups: int = 1                # sigmoid: groups kept per token
    routed_scale: float = 1.0           # sigmoid: routed_scaling_factor
    # the experts this layer holds: [expert_offset, + experts_held) of
    # the n_experts routed over (0 held: all of them)
    expert_offset: int = 0
    experts_held: int = 0
    first_dense_layers: int = 0         # deepseek: dense FFN prefix
    moe_every: int = 1                  # jamba: MoE every 2nd layer
    # SSM / hybrid
    attn_every: int = 1                 # 1: all-attn; 0: none; 8: jamba
    attn_offset: int = 3                # position of attn layer in period
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    conv_width: int = 4
    d_inner: int = 0                    # 0 -> 2 * d_model
    # modality frontend (assignment: STUB — precomputed embeddings in)
    frontend: str = "none"              # none | vision_stub | audio_stub
    frontend_dim: int = 0
    # MLP flavour
    mlp: str = "silu_glu"               # silu_glu | gelu
    tie_embeddings: bool = False
    # §Perf beyond-paper optimizations (default off = paper-faithful
    # baseline)
    distributed_decode: bool = False    # partial-softmax decode combine
    head_parallel_decode: bool = False  # head-partitioned decode step:
    #                                     each shard runs its heads'
    #                                     full-depth attention + its
    #                                     slice of the output projection,
    #                                     one psum of (B,S,D) partials
    #                                     (launch/mesh_lowering.py)
    moe_local_dispatch: bool = False    # route+scatter per shard inside
    #                                     shard_map (per-device capacity;
    #                                     only the EP all-to-all crosses
    #                                     chips)
    moe_shard_map_ep: bool = False      # explicit EP via shard_map
    #                                     all-to-alls (weights pinned)
    moe_expert_major_dispatch: bool = False  # pure-EP: dispatch buffer
    #                                     sharded expert-first so expert
    #                                     weights never move (pair with
    #                                     rules experts=("model","data"))
    moe_group_size: int = 0             # 0: group/batch-row; >0: token
    #                                     groups sharded over ALL axes
    #                                     (16x smaller EP all-to-all)
    # numerics / compilation
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"                 # none | full | dots_saveable
    scan_layers: bool = True
    attn_impl: str = "auto"
    attn_block_q: Optional[int] = None
    attn_block_k: Optional[int] = None
    ssd_chunk: int = 128
    max_seq_len: int = 524288

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_head or (self.d_model // self.n_heads)

    @property
    def held_experts(self) -> int:
        """How many experts this layer's weights hold."""
        return self.experts_held or self.n_experts

    @property
    def inner_dim(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    # ---- layer pattern (hybrid archs) -------------------------------
    def block_kind(self, i: int) -> str:
        if self.attn_every == 0:
            return "mamba"
        if self.attn_every == 1:
            return "attn"
        return "attn" if i % self.attn_every == self.attn_offset else "mamba"

    def ffn_kind(self, i: int) -> str:
        if not self.moe or i < self.first_dense_layers:
            return "dense"
        return "moe" if (i - self.first_dense_layers) % self.moe_every \
            == self.moe_every - 1 or self.moe_every == 1 else "dense"

    @property
    def layer_period(self) -> int:
        """Smallest repeating pattern of (block, ffn) kinds after the
        dense prefix — the scan unit."""
        p = 1
        if self.attn_every > 1:
            p = self.attn_every
        if self.moe and self.moe_every > 1:
            p = _lcm(p, self.moe_every)
        return p

    @property
    def n_periods(self) -> int:
        body = self.n_layers - self.first_dense_layers
        assert body % self.layer_period == 0, \
            f"{self.name}: {body} layers not divisible by period " \
            f"{self.layer_period}"
        return body // self.layer_period


def _lcm(a: int, b: int) -> int:
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Param:
    value: Any
    axes: tuple

    def tree_flatten(self):  # manual pytree-free: handled by split
        raise TypeError("split_params before using in jax")


def is_param(x) -> bool:
    return isinstance(x, Param)


def split_params(tree):
    """(values, logical_axes) with identical structure."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def param(key, shape, axes, dtype, scale: Optional[float] = None) -> Param:
    """Truncated-normal init with 1/sqrt(fan_in) default scale."""
    if scale is None:
        fan_in = shape[0] if len(shape) > 1 else shape[-1]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    v = scale * jax.random.truncated_normal(
        key, -2.0, 2.0, shape, jnp.float32)
    return Param(v.astype(dtype), axes)


def zeros_param(shape, axes, dtype) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def ones_param(shape, axes, dtype) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dt)


def rope(x, positions, theta: float, scaling: Optional[YaRN] = None):
    """Rotary embedding. x: (..., S, D) half-split rotation on the last
    dim; positions: (..., S) int32.  ``scaling``: YaRN frequencies and
    cos/sin scale (None, or a factor of 1, is plain RoPE bit for
    bit)."""
    d = x.shape[-1]
    half = d // 2
    yarn = scaling is not None and scaling.factor != 1
    if yarn:
        freqs = scaling.inv_freq(d, theta)
    else:
        freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32)
                        * (math.log(theta) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs   # (...,S,half)
    # insert head axes between batch and seq to match x's rank
    while ang.ndim < x.ndim:
        ang = jnp.expand_dims(ang, -3)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn and scaling.cos_scale != 1.0:
        cos, sin = cos * scaling.cos_scale, sin * scaling.cos_scale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def mlp_forward(params, x, kind: str):
    """Gated-SiLU or GELU MLP; hidden dim sharded on 'mlp' (TP)."""
    dt = x.dtype
    if kind == "silu_glu":
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(dt))
        u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u
    else:
        h = jnp.einsum("...d,df->...f", x, params["w_up"].astype(dt))
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(dt)
    h = constrain(h, "batch", "seq", "mlp")
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(dt))


def init_mlp(key, d_model: int, d_ff: int, kind: str, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": param(ks[0], (d_model, d_ff), ("embed", "mlp"), dtype),
         "w_down": param(ks[1], (d_ff, d_model), ("mlp", "embed"), dtype)}
    if kind == "silu_glu":
        p["w_gate"] = param(ks[2], (d_model, d_ff), ("embed", "mlp"), dtype)
    return p


def cross_entropy(logits, targets, mask=None):
    """Token-mean xent; logits f32, vocab possibly sharded on 'model'."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None],
                             axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
