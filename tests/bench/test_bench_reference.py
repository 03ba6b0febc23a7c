"""The plain float32 reference (bench/harness/reference.py with the GQA
architecture's bench/arch/gqa.py) against the engine's
prefill-then-decode logits through the same paged path, on the CPU at a
tiny size, in both of the benchmark's model forms."""

import numpy as np
import pytest

from conftest import make_tiny_root
from harness import cell, reference
from harness.manifest import Manifest

#: both sides compute in float32 on the CPU; they differ only in the
#: order of sums (chunked prefill, paged gather, blocked softmax), which
#: moves a logit of size ~1 by ~1e-6.  1e-4 leaves room for 16-bit-free
#: rounding drift and fails any real difference (a wrong position, norm
#: or head mapping moves logits by ~1e-1).
ATOL = 1e-4


def _logging_engine():
    from repro.serve import PagedContinuousBatchingEngine

    class Logged(PagedContinuousBatchingEngine):
        """Keeps each live row's decode logits by (request, position of
        the token fed)."""

        def begin_prefill(self, slot, prompt):
            super().begin_prefill(slot, prompt)
            self.__dict__.setdefault("slot_uid", {})[slot] = \
                self.uid_of_prompt[tuple(prompt)]

        def decode_once(self):
            toks = super().decode_once()
            if toks is not None:
                log = self.__dict__.setdefault("logged", {})
                for i, live in enumerate(self.live):
                    if live:
                        log[(self.slot_uid[i], self.row_ctx[i] - 1)] = \
                            np.array(self.last_logits[i], np.float32)
            return toks
    return Logged


@pytest.mark.parametrize("mlp,qk_norm", [("gelu_pytorch_tanh", False),
                                         ("silu", True)])
def test_reference_matches_engine_logits(tmp_path, mlp, qk_norm):
    import jax.numpy as jnp
    man = Manifest(make_tiny_root(tmp_path, mlp=mlp, qk_norm=qk_norm))
    ses = cell.Session(man, "tiny.mix", 5, compile_cache=False,
                       engine_base=_logging_engine())
    s = ses.serve(ses.plan(5), 1.5)
    logged = ses.engine.logged
    reqs = s["driver"].reqs
    ses.free_engine()
    uids = sorted({u for u, _ in logged})[:4]
    assert uids
    S = reference.QBLOCK
    R = max(len([1 for (u, _) in logged if u == v]) for v in uids)
    toks = np.zeros((len(uids), S), np.int32)
    pos = np.zeros((len(uids), R), np.int32)
    want = {}
    for j, u in enumerate(uids):
        seq = reqs[u].prompt + reqs[u].generated
        toks[j, :len(seq)] = seq
        ps = sorted(p for (v, p) in logged if v == u)
        pos[j, :len(ps)] = ps
        want[j] = [logged[(u, p)] for p in ps]
    h = ses.arch.final_hidden(ses.params, ses.dims, jnp.asarray(toks),
                              jnp.asarray(pos))
    got = np.asarray(jnp.einsum("nre,ev->nrv", h,
                                ses.params["lm_head"].astype(jnp.float32)))
    n = 0
    for j, rows in want.items():
        for r, w in enumerate(rows):
            np.testing.assert_allclose(got[j, r], w, atol=ATOL, rtol=0)
            n += 1
    assert n >= 8


def test_blocked_attention_matches_unblocked():
    """The reference attends in blocks of QBLOCK query rows; over several
    blocks it must equal the same layer computed in one piece."""
    import jax.numpy as jnp
    gqa = Manifest().arch("gqa")
    d = gqa.Dims(layers=1, d_model=32, heads=4, kv_heads=2, head_dim=8,
                 d_ff=64, vocab=64, mlp="silu_glu", qk_norm=True,
                 rope_theta=1e4, eps=1e-6, dtype="float32")
    p = gqa.make_params(d, 1)
    S = 3 * reference.QBLOCK
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, S)),
                       jnp.int32)
    pos = jnp.asarray(np.random.default_rng(1).integers(0, S, (2, 16)),
                      jnp.int32)
    got = gqa.final_hidden(p, d, toks, pos)
    # one block spanning the whole sequence: the same math unblocked
    old = gqa.QBLOCK
    try:
        gqa.QBLOCK = S
        gqa._layer.cache_clear()
        want = gqa.final_hidden(p, d, toks, pos)
    finally:
        gqa.QBLOCK = old
        gqa._layer.cache_clear()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
