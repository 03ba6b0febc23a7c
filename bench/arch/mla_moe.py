"""DeepSeek-V3's block: multi-head latent attention (MLA) with YaRN RoPE,
leading dense layers, then mixture-of-experts layers with a sigmoid,
group-limited router, a chip's share of the routed experts and one
shared expert.  Found by a configuration file's ``"arch": "mla_moe"``;
the harness calls the names ``bench/arch/gqa.py`` lists.

The reference computes the model as the published description defines
it (arXiv:2412.19437, §2.1), from the weights ``make_params`` made:

    x = embed[tokens]
    per layer:  h = rms(x) * pre_norm
                c_q = rms(h W_qa) * q_a_norm
                q_nope, q_rope = split(c_q W_qb)         (per head)
                c_kv, k_rope = split(h W_kva)
                c_kv = rms(c_kv) * kv_a_norm
                q_rope, k_rope = yarn_rope(q_rope), yarn_rope(k_rope)
                k = [c_kv W_kb, k_rope] per head;  v = c_kv W_vb per head
                x = x + softmax(q k^T * s, causal) v Wo
                    with s = (d_nope + d_rope)^-1/2 * mscale^2
                h = rms(x) * ffn_norm
                dense layer:  x = x + W_down(silu(h W_gate) * h W_up)
                MoE layer:    sc = sigmoid(h W_r) over all routed experts
                              b = sc + bias; groups ranked by the sum of
                              their two best b, the best topk_group kept;
                              the top-k b among them are chosen, weighted
                              by sc normalised over the k, times the
                              routed scale
                              x = x + sum over chosen held experts e of
                                  w_e E_e(h) + E_shared(h)
    logits = (rms(x) * final_norm) lm_head

non-absorbed: K and V are expanded per head from the latent, as training
computes them (the program decodes absorbed, from a latent cache).  YaRN
(arXiv:2309.00071) as DeepSeek's code has it: frequency i keeps
base^(-2i/d) below the beta_fast correction dimension, is divided by the
factor above beta_slow's, and ramps linearly between; cos and sin are
scaled by m(mscale) / m(mscale_all_dim), m(a) = 0.1 a ln(factor) + 1.

The held experts are this chip's share of the routed experts (the first
``n_routed_experts`` of ``published.n_routed_experts``): what the absent
experts would add lies on other chips and is left out here as in the
program.  Every product runs in float32 at ``Precision.HIGHEST``, one
layer and one sequence at a time, attention in blocks of heads and
query rows, so the reference fits beside the weights.  The control
(``lowp``) rounds to fp8 where the program rounds to bf16.

A router's choice is discontinuous.  Where two experts' biased scores
(or two groups' scores) lie within rounding of each other, the bf16
program and the float32 reference may choose differently, and one held
expert's share then moves that position's logits by several times what
rounding moves them elsewhere.  Either choice is the published rule's,
up to rounding.  So, as the check teacher-forces the served tokens, the
reference takes the program's choice of experts where the program kept
it (``repro.serve.tracing.routes``, which ``program_config`` turns on):
at each decoded position and MoE layer, if that choice is the rule's
top-k under some change of each biased score by at most ``TIE``.  The
weights stay the reference's own scores over the chosen experts.  A
choice further off is not taken, and is counted (``route``), so a wrong
router still shows in the logits.
"""

from __future__ import annotations

import dataclasses
import functools
import math

from harness.counts import causal_cols
from harness.model import key_for
from harness.reference import QBLOCK, _fp8, _hi, _rms

#: query heads a reference attention block holds
HBLOCK = 16


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the benchmark's names."""
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    q_lora: int
    kv_lora: int
    qk_nope: int
    qk_rope: int
    v_head: int
    d_ff: int
    d_expert: int
    routed: int                 # experts the router scores
    held: int                   # experts held here: [0, held)
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float
    shared: int
    vocab: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        y = c["rope_scaling"]
        return cls(layers=int(c["num_hidden_layers"]),
                   dense_layers=int(c["first_k_dense_replace"]),
                   d_model=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   q_lora=int(c["q_lora_rank"]),
                   kv_lora=int(c["kv_lora_rank"]),
                   qk_nope=int(c["qk_nope_head_dim"]),
                   qk_rope=int(c["qk_rope_head_dim"]),
                   v_head=int(c["v_head_dim"]),
                   d_ff=int(c["intermediate_size"]),
                   d_expert=int(c["moe_intermediate_size"]),
                   routed=int(c["published"]["n_routed_experts"]),
                   held=int(c["n_routed_experts"]),
                   top_k=int(c["num_experts_per_tok"]),
                   n_group=int(c["n_group"]),
                   topk_group=int(c["topk_group"]),
                   routed_scale=float(c["routed_scaling_factor"]),
                   shared=int(c["n_shared_experts"]),
                   vocab=int(c["vocab_size"]),
                   rope_theta=float(c["rope_theta"]),
                   yarn_factor=float(y["factor"]),
                   yarn_original=int(y["original_max_position_embeddings"]),
                   yarn_beta_fast=float(y["beta_fast"]),
                   yarn_beta_slow=float(y["beta_slow"]),
                   yarn_mscale=float(y["mscale"]),
                   yarn_mscale_all_dim=float(y["mscale_all_dim"]),
                   eps=float(c["rms_norm_eps"]),
                   dtype=c["torch_dtype"])

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def latent(self) -> int:
        """A token's cached latent: the compressed KV and the rotary key."""
        return self.kv_lora + self.qk_rope

    # -- counts (parameters) -----------------------------------------------

    @property
    def attn_params(self) -> int:
        d, h = self.d_model, self.heads
        return (d * self.q_lora + self.q_lora * h * (self.qk_nope
                                                     + self.qk_rope)
                + d * self.latent + self.kv_lora * h * (self.qk_nope
                                                        + self.v_head)
                + h * self.v_head * d)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert


def dims(cfg: dict) -> Dims:
    # a program that cannot serve the configuration fails here, before
    # any weight is made
    program_config(cfg)
    return Dims.from_config(cfg)


def describe(d: Dims) -> str:
    return (f"{d.layers} layers ({d.dense_layers} dense), d_model "
            f"{d.d_model}, MLA {d.heads} heads over a {d.latent}-wide "
            f"latent, {d.held} of {d.routed} experts top-{d.top_k} + "
            f"{d.shared} shared, vocab {d.vocab}, {d.dtype}")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file.  It also
    asks the program to keep its routing, which the reference takes at
    near ties (module doc)."""
    from repro.models.common import ModelConfig, YaRN
    from repro.serve import tracing
    tracing.keep_routes()
    d = Dims.from_config(cfg)
    return ModelConfig(
        name=cfg["name"], n_layers=d.layers, d_model=d.d_model,
        n_heads=d.heads, n_kv_heads=d.heads, d_head=d.qk_nope + d.qk_rope,
        d_ff=d.d_ff, vocab_size=d.vocab, attention="mla",
        q_lora_rank=d.q_lora, kv_lora_rank=d.kv_lora,
        qk_rope_head_dim=d.qk_rope, qk_nope_head_dim=d.qk_nope,
        v_head_dim=d.v_head, moe=True, n_experts=d.routed, top_k=d.top_k,
        d_expert=d.d_expert, n_shared_experts=d.shared,
        first_dense_layers=d.dense_layers, router="sigmoid",
        n_groups=d.n_group, topk_groups=d.topk_group,
        routed_scale=d.routed_scale, expert_offset=0, experts_held=d.held,
        rope_theta=d.rope_theta,
        rope_scaling=YaRN(factor=d.yarn_factor,
                          original_max_position=d.yarn_original,
                          beta_fast=d.yarn_beta_fast,
                          beta_slow=d.yarn_beta_slow, mscale=d.yarn_mscale,
                          mscale_all_dim=d.yarn_mscale_all_dim),
        mlp="silu_glu", tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=d.dtype, compute_dtype=d.dtype)


def make_params(d: Dims, seed: int):
    """Every weight from ``seed`` in one jitted call, in ``d.dtype``, as
    the program's tree: the dense layers under ``prefix_layers``, the
    MoE layers stacked under ``layers``.

    Matrices are normal with 1/sqrt(fan-in) scale, and those that write
    into the residual stream (Wo, every W_down) a further 1/sqrt(2 x
    layers); norm weights 1 + N(0, 0.1); the router float32, its bias
    N(0, 0.05^2) (a trained one is learned to balance the load)."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(d.dtype)
    E, H, V = d.d_model, d.heads, d.vocab
    branch = 2 * d.layers

    def build(key):
        ks = iter(jax.random.split(key, 64))

        def mat(shape, fan_in, dtype=dt):
            return (jax.random.normal(next(ks), shape, dtype)
                    * jnp.asarray(1.0 / math.sqrt(fan_in), dtype))

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dt)

        def attn(L):
            return {
                "wq_a": mat(L + (E, d.q_lora), E),
                "q_a_norm": norm(L + (d.q_lora,)),
                "wq_b": mat(L + (d.q_lora, H, d.qk_nope + d.qk_rope),
                            d.q_lora),
                "wkv_a": mat(L + (E, d.latent), E),
                "kv_a_norm": norm(L + (d.kv_lora,)),
                "wk_b": mat(L + (d.kv_lora, H, d.qk_nope), d.kv_lora),
                "wv_b": mat(L + (d.kv_lora, H, d.v_head), d.kv_lora),
                "wo": mat(L + (H, d.v_head, E), H * d.v_head * branch)}

        def mlp(L, F):
            return {"w_gate": mat(L + (E, F), E), "w_up": mat(L + (E, F), E),
                    "w_down": mat(L + (F, E), F * branch)}

        def layer(L, ffn):
            return {"pre_norm": norm(L + (E,)), "attn": attn(L),
                    "ffn_norm": norm(L + (E,)), **ffn}

        prefix = [layer((), {"mlp": mlp((), d.d_ff)})
                  for _ in range(d.dense_layers)]
        M = (d.moe_layers,)
        moe = {"router": mat(M + (E, d.routed), E, jnp.float32),
               "router_bias": 0.05 * jax.random.normal(
                   next(ks), M + (d.routed,), jnp.float32),
               "w_gate": mat(M + (d.held, E, d.d_expert), E),
               "w_up": mat(M + (d.held, E, d.d_expert), E),
               "w_down": mat(M + (d.held, d.d_expert, E),
                             d.d_expert * branch),
               "shared": mlp(M, d.d_expert * d.shared)}
        return {"embed": jax.random.normal(next(ks), (V, E), dt),
                "prefix_layers": prefix,
                "layers": [layer(M, {"moe": moe})],
                "final_norm": norm((E,)),
                "lm_head": mat((E, V), E)}

    return jax.jit(build)(key_for(seed))


# -- the plain reference -------------------------------------------------


def yarn_inv_freq(d: Dims):
    """(qk_rope/2,) float32 frequencies of YaRN (module doc)."""
    import jax.numpy as jnp
    dim, base = d.qk_rope, d.rope_theta
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = 1.0 / base ** (2.0 * i / dim)
    inter = extra / d.yarn_factor

    def find(rot):
        return dim * math.log(d.yarn_original / (2 * math.pi * rot)) \
            / (2 * math.log(base))
    low = max(math.floor(find(d.yarn_beta_fast)), 0)
    high = min(math.ceil(find(d.yarn_beta_slow)), dim - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def _m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(d: Dims) -> float:
    return (d.qk_nope + d.qk_rope) ** -0.5 \
        * _m(d.yarn_factor, d.yarn_mscale_all_dim) ** 2


def _yarn_rope(x, pos, d: Dims):
    """Half-split rotation of x (..., S, D) at positions pos (S,)."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(d)
    scale = _m(d.yarn_factor, d.yarn_mscale) \
        / _m(d.yarn_factor, d.yarn_mscale_all_dim)
    c, s = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def route(d: Dims, logits, bias, forced=None, has=None):
    """The published router over (T, routed) float32 logits: (weights,
    ids) of the top-k, each (T, k), and (T, 4): the margins a rounding
    would have to cross to change the choice (between the k-th and
    (k+1)-th biased scores in the kept groups, and between the
    topk_group-th and next group scores), then 1 where the program's
    choice ``forced`` (T, k), given where ``has`` (T,), differed from
    the rule's and was taken, and 1 where it differed and was not
    (``admissible``)."""
    import jax
    import jax.numpy as jnp
    t = logits.shape[0]
    sc = jax.nn.sigmoid(logits)
    bf = sc + bias
    b = bf.reshape(t, d.n_group, d.routed // d.n_group)
    gs = jax.lax.top_k(b, 2)[0].sum(-1)
    gtop, keep = jax.lax.top_k(gs, min(d.topk_group + 1, d.n_group))
    keep = keep[:, :d.topk_group]
    gmargin = (gtop[:, d.topk_group - 1] - gtop[:, d.topk_group]
               if d.topk_group < d.n_group else jnp.full((t,), jnp.inf))
    kept = jax.nn.one_hot(keep, d.n_group).sum(1) > 0
    b = jnp.where(kept[..., None], b, -jnp.inf).reshape(t, d.routed)
    top, idx = jax.lax.top_k(b, d.top_k + 1)
    idx = idx[:, :d.top_k]
    took = refused = jnp.zeros((t,), bool)
    if forced is not None:
        ok = admissible(d, bf, gs, forced)
        differs = has & (jnp.sort(forced, -1) != jnp.sort(idx, -1)).any(-1)
        took, refused = differs & ok, differs & ~ok
        idx = jnp.where((has & ok)[:, None], forced, idx)
    w = jnp.take_along_axis(sc, idx, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * d.routed_scale
    return w, idx, jnp.stack(
        [top[:, d.top_k - 1] - top[:, d.top_k], gmargin,
         took.astype(jnp.float32), refused.astype(jnp.float32)], -1)


def admissible(d: Dims, bf, gs, ids):
    """(T,) bool: whether the experts ``ids`` (T, k) are the published
    rule's choice once each biased score of ``bf`` (T, routed) moves by
    at most ``TIE`` (so a group score of ``gs`` (T, n_group), a sum of
    two, by at most 2 TIE): k distinct experts in at most topk_group
    groups, those groups (filled up with the best others) a top set of
    group scores, and the experts a top-k among the kept groups."""
    import jax
    import jax.numpy as jnp
    t, per = ids.shape[0], d.routed // d.n_group
    inf = jnp.inf
    chosen = jax.nn.one_hot(ids, d.routed).sum(1) > 0          # (T, routed)
    gchosen = chosen.reshape(t, d.n_group, per).any(-1)         # (T, G)
    _, best = jax.lax.top_k(jnp.where(gchosen, inf, gs), d.topk_group)
    gkeep = jax.nn.one_hot(best, d.n_group).sum(1) > 0
    ok_groups = (gchosen.sum(-1) <= d.topk_group) & (
        jnp.where(gkeep, gs, inf).min(-1)
        - jnp.where(gkeep, -inf, gs).max(-1) >= -4 * TIE)
    elig = jnp.repeat(gkeep, per, axis=-1)
    ok_experts = (jnp.where(chosen, bf, inf).min(-1)
                  - jnp.where(elig & ~chosen, bf, -inf).max(-1)
                  >= -2 * TIE)
    return (chosen.sum(-1) == d.top_k) & ok_groups & ok_experts


def _sum_over(fn, xs, shape):
    """sum of fn(x) over xs, accumulated (never all held at once)."""
    import jax
    import jax.numpy as jnp
    return jax.lax.scan(lambda acc, x: (acc + fn(x), None),
                        jnp.zeros(shape, jnp.float32), xs)[0]


@functools.lru_cache(maxsize=None)
def _layer(d: Dims, lowp: bool, moe: bool):
    """One layer over one sequence x (S, E), with the program's choice
    of experts ``forced`` (S, k) where ``has`` (S,): (x, the router's
    (S, 4), see ``route``; margins inf and counts 0 in a dense
    layer)."""
    import jax
    import jax.numpy as jnp
    r = _fp8 if lowp else (lambda a: a)

    def f32(a):
        return a.astype(jnp.float32)

    def mm(eq, a, b):
        return r(jnp.einsum(eq, a, r(f32(b)), precision=_hi()))

    def layer(x, lp, i, forced=None, has=None):
        # the layer's weights stay bf16; each is cast where it is used
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, keepdims=False), lp)
        S, E = x.shape
        H, nope = d.heads, d.qk_nope
        pos = jnp.arange(S)
        h = r(_rms(x, f32(w["pre_norm"]), d.eps))
        a = w["attn"]
        cq = r(_rms(mm("se,er->sr", h, a["wq_a"]), f32(a["q_a_norm"]),
                    d.eps))
        ckv = mm("se,er->sr", h, a["wkv_a"])
        c = r(_rms(ckv[:, :d.kv_lora], f32(a["kv_a_norm"]), d.eps))
        k_rope = r(_yarn_rope(ckv[None, :, d.kv_lora:], pos, d))[0]
        scale = softmax_scale(d)
        hb = min(HBLOCK, H)

        def head_block(g):
            hs = jax.lax.dynamic_slice_in_dim(jnp.arange(H), g, hb)
            wqb = jnp.take(a["wq_b"], hs, axis=1)
            q = mm("sr,rhd->hsd", cq, wqb)
            q = jnp.concatenate(
                [q[..., :nope], r(_yarn_rope(q[..., nope:], pos, d))], -1)
            k = jnp.concatenate(
                [mm("sr,rhd->hsd", c, jnp.take(a["wk_b"], hs, axis=1)),
                 jnp.broadcast_to(k_rope, (hb, S, d.qk_rope))], -1)
            v = mm("sr,rhd->hsd", c, jnp.take(a["wv_b"], hs, axis=1))

            def q_block(q0):
                qb = jax.lax.dynamic_slice_in_dim(q, q0, QBLOCK, axis=1)
                s = jnp.einsum("hqd,hsd->hqs", qb, k,
                               precision=_hi()) * scale
                rows = q0 + jnp.arange(QBLOCK)[:, None]
                s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
                p = r(jax.nn.softmax(s, axis=-1))
                return jnp.einsum("hqs,hsd->hqd", p, v, precision=_hi())

            o = jax.lax.map(q_block, jnp.arange(0, S, QBLOCK))
            o = r(jnp.moveaxis(o, 0, 1).reshape(hb, S, d.v_head))
            return jnp.einsum("hsd,hde->se", o,
                              r(f32(jnp.take(a["wo"], hs, axis=0))),
                              precision=_hi())

        attn = _sum_over(head_block, jnp.arange(0, H, hb), (S, E))
        x = r(x + r(attn))
        h = r(_rms(x, f32(w["ffn_norm"]), d.eps))

        def expert(m, hh):
            """A SiLU-GLU MLP over hh (S, E), QBLOCK rows at a time."""
            def rows(hb_):
                g = mm("se,ef->sf", hb_, m["w_gate"])
                u = mm("se,ef->sf", hb_, m["w_up"])
                return mm("sf,fe->se", r(r(jax.nn.sigmoid(g) * g) * u),
                          m["w_down"])
            out = jax.lax.map(rows, hh.reshape(S // QBLOCK, QBLOCK, E))
            return out.reshape(S, E)

        if not moe:
            return r(x + expert(w["mlp"], h)), jnp.tile(
                jnp.array([jnp.inf, jnp.inf, 0.0, 0.0]), (S, 1))
        m = w["moe"]
        logits = jnp.einsum("se,en->sn", h, f32(m["router"]),
                            precision=_hi())
        wt, idx, margin = route(d, logits, m["router_bias"], forced, has)
        gate = (jax.nn.one_hot(idx, d.held) * wt[..., None]).sum(1)

        def held(e):
            one = jax.tree.map(lambda a: a[e], {k: m[k] for k in
                                                ("w_gate", "w_up",
                                                 "w_down")})
            return expert(one, h) * jax.lax.dynamic_slice_in_dim(
                gate, e, 1, axis=1)

        routed = _sum_over(held, jnp.arange(d.held), (S, E))
        y = r(routed) + expert(m["shared"], h)
        return r(x + y), margin

    return jax.jit(layer)


def _hidden(params, d: Dims, seq, lowp: bool, forced=None, has=None):
    """One sequence (S,) through every layer, with the program's choice
    of experts ``forced`` (S, moe_layers, k) where ``has`` (S,): (x (S,
    E), the router's (S, moe_layers, 4), see ``route``)."""
    import jax
    import jax.numpy as jnp
    r = _fp8 if lowp else (lambda a: a)
    x = r(params["embed"][seq].astype(jnp.float32))
    for lp in params["prefix_layers"]:
        x, _ = _layer(d, lowp, False)(
            x, jax.tree.map(lambda a: a[None], lp), 0)
    lp = params["layers"][0]
    margins = []
    for i in range(d.moe_layers):
        x, mg = _layer(d, lowp, True)(
            x, lp, i, None if forced is None else forced[:, i], has)
        margins.append(mg)
    return x, jnp.stack(margins, 1)


#: how far rounding may move one biased score, for ``admissible``: bf16's
#: epsilon.  The program's router input carries a bf16 residual stream's
#: rounding through every layer before it; the widest margin at which a
#: flipped choice was read on a TPU v5e, at this cell's size, was 3.2e-3,
#: a fifth of 2 TIE.
TIE = 2.0 ** -7


def program_routes(d: Dims, tokens, read_pos):
    """The program's choice of experts for each row of ``tokens`` (n,
    S), from what it kept (``repro.serve.tracing.routes``, keyed by the
    prompt, which ends where ``read_pos`` starts): (n, S, moe_layers,
    k) int32 and (n, S) bool where it is given.  None where the program
    keeps no routing."""
    import numpy as np
    try:
        from repro.serve import tracing
    except ImportError:
        return None
    kept = getattr(tracing, "routes", None)
    if kept is None:
        return None
    tokens, read_pos = np.asarray(tokens), np.asarray(read_pos)
    n, S = tokens.shape
    forced = np.zeros((n, S, d.moe_layers, d.top_k), np.int32)
    has = np.zeros((n, S), bool)
    for j in range(n):
        log = kept.get(tuple(tokens[j, :int(read_pos[j, 0]) + 1].tolist()))
        for pos, ids in (log or {}).items():
            if pos < S:
                forced[j, pos], has[j, pos] = ids, True
    return forced, has


def hidden_and_margins(params, d: Dims, tokens, read_pos,
                       lowp: bool = False, routes=None):
    """The normed final hidden state at ``read_pos`` (n, R) of each of
    ``tokens`` (n, S), S a multiple of ``QBLOCK``: (n, R, E) float32,
    and the router's (n, R, moe_layers, 4) there (``route``): the
    8th/9th expert and 4th/5th group margins, and whether the program's
    choice, from ``routes`` (``program_routes``), differed and was
    taken, or differed and was not."""
    import jax.numpy as jnp
    assert tokens.shape[1] % QBLOCK == 0
    r = _fp8 if lowp else (lambda a: a)
    outs, margins = [], []
    for j in range(tokens.shape[0]):
        forced, has = (None, None) if routes is None else (
            jnp.asarray(routes[0][j]), jnp.asarray(routes[1][j]))
        x, mg = _hidden(params, d, tokens[j], lowp, forced, has)
        outs.append(jnp.take(x, read_pos[j], axis=0))
        margins.append(jnp.take(mg, read_pos[j], axis=0))
    x = jnp.stack(outs)
    return (r(_rms(x, params["final_norm"].astype(jnp.float32), d.eps)),
            jnp.stack(margins))


def final_hidden(params, d: Dims, tokens, read_pos, lowp: bool = False):
    """``hidden_and_margins``' hidden state; the reference (not the
    control) takes the program's routing at near ties (module doc).  It
    also prints, over the read positions (position 0, where the check
    pads, left out), how many took the program's choice in some MoE
    layer and how many had one too far off to take."""
    import numpy as np
    routes = None if lowp else program_routes(d, tokens, read_pos)
    x, info = hidden_and_margins(params, d, tokens, read_pos, lowp, routes)
    if not lowp:
        real = np.asarray(read_pos) > 0
        took, refused = np.asarray(info[..., 2:] > 0).any(-2).transpose(
            2, 0, 1)
        kept = real & (False if routes is None else np.take_along_axis(
            routes[1], np.asarray(read_pos), 1))
        print(f"reference: of {int(real.sum())} read positions, "
              f"{int((real & took).sum())} took the program's choice of "
              f"experts at a tie within {TIE} and "
              f"{int((real & refused).sum())} had a choice further off; "
              f"the program kept its routing at {int(kept.sum())} of "
              f"them", flush=True)
    return x


# -- counts ----------------------------------------------------------------


def step_flops(d: Dims, span) -> float:
    """Model FLOPs of one decode step or one prefill chunk: 2 x the
    parameters a token multiplies (attention, the dense layers' MLP, the
    shared expert, and the held experts at their expectation: a token
    picks each of ``routed`` experts with chance top_k / routed, so the
    ``held`` here see ``held x top_k / routed`` of its pairs), the
    logits head per row of logits, and absorbed attention over each
    row's context (its scores over the latent, P.V over kv_lora)."""
    dense = 2 * (d.layers * d.attn_params
                 + d.dense_layers * 3 * d.d_model * d.d_ff
                 + d.moe_layers * (d.shared + d.held * d.top_k / d.routed)
                 * d.expert_params)
    head = 2 * d.d_model * d.vocab
    attn = 2 * d.layers * d.heads * (d.latent + d.kv_lora)
    if span.kind == "decode":
        return span.rows * (dense + head) + attn * sum(span.contexts)
    return (span.rows * dense + head
            + attn * causal_cols(span.rows, span.offset))


def layer_calls(d: Dims, path: str) -> int:
    """Every layer's attention runs the kernel of its path once."""
    return d.layers
