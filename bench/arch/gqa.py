"""The dense GQA block of starcoder2-7b and qwen3-8b: every fact of the
architecture that the benchmark needs, found by a configuration file's
``"arch": "gqa"``.

An architecture module is the one place the benchmark learns a model
family.  ``bench/harness/manifest.py`` loads ``bench/arch/<arch>.py`` for
the ``arch`` key of a configuration file, and the harness calls only
these names of it:

* ``dims(cfg) -> Dims``: the configuration file's sizes under the
  benchmark's names.  ``Dims`` is a frozen, hashable dataclass (the
  reference caches its compiled layer on it) with at least ``vocab``
  (the traffic draws token ids below it); the fields the kernel count
  modules of ``bench/kernels/`` read are the module's to give (here
  ``d_model``, ``heads``, ``kv_heads``, ``head_dim``).
* ``describe(d) -> str``: the sizes, for the set-up line a run prints.
* ``make_params(d, seed)``: every weight from ``seed`` in one jitted call
  on the device, in the dtype it is served in, laid out as the program's
  parameter tree expects, with ``lm_head`` (d_model, vocab) at the top
  (the check's logits head).
* ``program_config(cfg)``: the program's ``ModelConfig``.
* ``final_hidden(params, d, tokens, read_pos, lowp=False)``: the plain
  float32 reference's normed final hidden state (n, R, d_model) at
  ``read_pos`` (n, R) of ``tokens`` (n, S), S a multiple of
  ``reference.QBLOCK``; with ``lowp``, the fp8 control's.  It imports
  nothing of the program.
* ``step_flops(d, span) -> float``: model FLOPs of one decode step or
  prefill chunk recorded by the harness (``harness.recorder.Span``).
* ``layer_calls(d, path) -> int``: how many layer calls of one step go
  through the kernel of dispatch path ``path``.

The reference computes the model as the repository defines it, from the
weights ``make_params`` made:

    x = embed[tokens]
    per layer:  h = rms(x) * pre_norm
                q, k, v = h Wq, h Wk, h Wv            (GQA: kv_heads < heads)
                q, k = rms(q) * q_norm, rms(k) * k_norm   (qk-norm configs)
                q, k = rope(q), rope(k)               (half-split rotation)
                x = x + softmax(q k^T / sqrt(head_dim), causal) v Wo
                h = rms(x) * ffn_norm
                x = x + W_down gelu_tanh(h W_up)      (or silu(h W_gate) * h W_up)
    logits = (rms(x) * final_norm) lm_head

with RMSNorm ``x / sqrt(mean(x^2) + eps)``.  Every product runs in
float32 at ``Precision.HIGHEST``; each layer's bf16 weights are cast to
float32 inside the layer's program, one layer at a time, so the
reference fits beside the weights.  The control (``lowp``) rounds to
fp8 where the program rounds to bf16: the weights, the embedding, each
product's output, each norm's output, q and k after RoPE, the softmax
weights, the residual stream after each add.
"""

from __future__ import annotations

import dataclasses
import functools
import math

from harness.counts import causal_cols
from harness.model import key_for
from harness.reference import QBLOCK, _fp8, _hi, _rms, _rope


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of one configuration file, under the benchmark's names."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mlp: str                    # "gelu" (tanh form) | "silu_glu"
    qk_norm: bool
    rope_theta: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        act = c["hidden_act"]
        mlp = {"gelu_pytorch_tanh": "gelu", "silu": "silu_glu"}[act]
        return cls(layers=int(c["num_hidden_layers"]),
                   d_model=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["head_dim"]),
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   mlp=mlp, qk_norm=bool(c.get("qk_norm", False)),
                   rope_theta=float(c["rope_theta"]),
                   eps=float(c.get("rms_norm_eps", c.get("norm_epsilon"))),
                   dtype=c["torch_dtype"])

    # -- counts (parameters) -----------------------------------------------

    @property
    def attn_params(self) -> int:
        d, h, hk, dh = self.d_model, self.heads, self.kv_heads, self.head_dim
        return d * h * dh + 2 * d * hk * dh + h * dh * d

    @property
    def mlp_params(self) -> int:
        return (3 if self.mlp == "silu_glu" else 2) * self.d_model * self.d_ff

    @property
    def layer_params(self) -> int:
        return self.attn_params + self.mlp_params


def dims(cfg: dict) -> Dims:
    return Dims.from_config(cfg)


def describe(d: Dims) -> str:
    return (f"{d.layers} layers, d_model {d.d_model}, {d.heads}/"
            f"{d.kv_heads} heads, vocab {d.vocab}, {d.dtype}")


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.common import ModelConfig
    d = Dims.from_config(cfg)
    return ModelConfig(
        name=cfg["name"], n_layers=d.layers, d_model=d.d_model,
        n_heads=d.heads, n_kv_heads=d.kv_heads, d_head=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, qk_norm=d.qk_norm,
        rope_theta=d.rope_theta, mlp=d.mlp,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=d.dtype, compute_dtype=d.dtype)


def make_params(d: Dims, seed: int):
    """Every weight from ``seed`` in one jitted call, in ``d.dtype``.

    Matrices are normal with 1/sqrt(fan-in) scale, and the two that
    write into the residual stream (Wo, W_down) a further 1/sqrt(2 x
    layers), as trained models are initialised; norm weights are
    1 + N(0, 0.1) so that a norm applied without its weight shows."""
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(d.dtype)
    L, E, H, K, Dh, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                            d.head_dim, d.d_ff, d.vocab)
    branch = 2 * L      # 1 / (residual branch scale)^2

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape, dt)
                    * jnp.asarray(1.0 / math.sqrt(fan_in), dt))

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape,
                                                  jnp.float32)).astype(dt)

        attn = {"wq": mat((L, E, H, Dh), E), "wk": mat((L, E, K, Dh), E),
                "wv": mat((L, E, K, Dh), E),
                "wo": mat((L, H, Dh, E), H * Dh * branch)}
        if d.qk_norm:
            attn["q_norm"] = norm((L, Dh))
            attn["k_norm"] = norm((L, Dh))
        mlp = {"w_up": mat((L, E, F), E),
               "w_down": mat((L, F, E), F * branch)}
        if d.mlp == "silu_glu":
            mlp["w_gate"] = mat((L, E, F), E)
        return {"embed": jax.random.normal(next(ks), (V, E), dt),
                "prefix_layers": [],
                "layers": [{"pre_norm": norm((L, E)), "attn": attn,
                            "ffn_norm": norm((L, E)), "mlp": mlp}],
                "final_norm": norm((E,)),
                "lm_head": mat((E, V), E)}

    return jax.jit(build)(key_for(seed))


# -- the plain reference -------------------------------------------------


@functools.lru_cache(maxsize=None)
def _layer(d: Dims, lowp: bool):
    import jax
    import jax.numpy as jnp
    r = _fp8 if lowp else (lambda x: x)

    def mm(eq, a, b):
        return r(jnp.einsum(eq, a, r(b), precision=_hi()))

    def layer(x, lp, i):
        w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, keepdims=False).astype(jnp.float32), lp)
        n, S, E = x.shape
        H, K, Dh = d.heads, d.kv_heads, d.head_dim
        pos = jnp.broadcast_to(jnp.arange(S), (n, S))
        h = r(_rms(x, w["pre_norm"], d.eps))
        a = w["attn"]
        q = mm("nse,ehd->nhsd", h, a["wq"])
        k = mm("nse,ehd->nhsd", h, a["wk"])
        v = mm("nse,ehd->nhsd", h, a["wv"])
        if d.qk_norm:
            q = r(_rms(q, a["q_norm"], d.eps))
            k = r(_rms(k, a["k_norm"], d.eps))
        q = r(_rope(q, pos, d.rope_theta)).reshape(n, K, H // K, S, Dh)
        k = r(_rope(k, pos, d.rope_theta))

        def block(qb_start):
            qb = jax.lax.dynamic_slice_in_dim(q, qb_start, QBLOCK, axis=3)
            s = jnp.einsum("nkgqd,nksd->nkgqs", qb, k,
                           precision=_hi()) * Dh ** -0.5
            rows = qb_start + jnp.arange(QBLOCK)[:, None]
            s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
            p = r(jax.nn.softmax(s, axis=-1))
            return jnp.einsum("nkgqs,nksd->nkgqd", p, v, precision=_hi())

        o = jax.lax.map(block, jnp.arange(0, S, QBLOCK))  # (nb,n,K,G,qb,D)
        o = r(jnp.moveaxis(o, 0, 3).reshape(n, H, S, Dh))
        x = r(x + mm("nhsd,hde->nse", o, a["wo"]))
        h = r(_rms(x, w["ffn_norm"], d.eps))
        m = w["mlp"]
        up = mm("nse,ef->nsf", h, m["w_up"])
        if d.mlp == "silu_glu":
            g = mm("nse,ef->nsf", h, m["w_gate"])
            act = r(r(jax.nn.sigmoid(g) * g) * up)
        else:
            act = r(0.5 * up * (1.0 + jnp.tanh(
                (2.0 / jnp.pi) ** 0.5 * (up + 0.044715 * up ** 3))))
        return r(x + mm("nsf,fe->nse", act, m["w_down"]))

    return jax.jit(layer)


def final_hidden(params, d: Dims, tokens, read_pos, lowp: bool = False):
    """The normed final hidden state at ``read_pos`` (n, R) of each of
    ``tokens`` (n, S), S a multiple of ``QBLOCK``: (n, R, E) float32."""
    import jax.numpy as jnp
    assert tokens.shape[1] % QBLOCK == 0
    r = _fp8 if lowp else (lambda x: x)
    x = r(params["embed"][tokens].astype(jnp.float32))
    lp = params["layers"][0]
    step = _layer(d, lowp)
    for i in range(d.layers):
        x = step(x, lp, i)
    x = jnp.take_along_axis(x, read_pos[..., None], axis=1)
    return r(_rms(x, params["final_norm"].astype(jnp.float32), d.eps))


# -- counts ----------------------------------------------------------------


def step_flops(d: Dims, span) -> float:
    """Model FLOPs of one decode step or one prefill chunk."""
    attn = 4 * d.layers * d.heads * d.head_dim
    dense = 2 * d.layers * d.layer_params
    head = 2 * d.d_model * d.vocab
    if span.kind == "decode":
        return span.rows * (dense + head) + attn * sum(span.contexts)
    return (span.rows * dense + head
            + attn * causal_cols(span.rows, span.offset))


def layer_calls(d: Dims, path: str) -> int:
    """Every layer is the same block: each kernel runs once a layer."""
    return d.layers
