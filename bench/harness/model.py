"""A configuration file's sizes, the program's config built from them,
and the weights the benchmark makes for both the program and the plain
reference.

The weights are the benchmark's own: one jitted call draws every leaf
from ``--seed`` on the device, in the dtype they are served in, laid
out as the program's parameter tree expects.  The reference reads the
same arrays; nothing the program makes is handed to it.
"""

from __future__ import annotations

import dataclasses
import math


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

    # -- counts (parameters, bytes) ----------------------------------------

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

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * 2


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


def key_for(seed: int):
    """A PRNG key from any whole seed (the driver's exceed 32 bits)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


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
