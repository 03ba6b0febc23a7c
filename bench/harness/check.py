"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed, a sample of the requests that were served
is drawn from the seed, with the longest of them always in it.  The
reference runs once over each sampled prompt with its served tokens
(teacher-forced).  Two numbers are compared with the cell's limits:

* ``logit_gap``: at each served position, how far the served token's
  logit lies below the reference's best, widest over the sample;
* ``logit_rel``: at each position served by a decode step, the L2
  distance of the logits the engine handed out from the reference's,
  over the reference's norm, largest over the sample.  Where random
  weights make greedy choices win by wide margins, no rounding moves a
  served token and the first number reads 0 for the program and its
  control alike; this one still separates them.

Greedy decoding in the configuration's precision stays within rounding
of the reference; a token computed wrongly, or in a coarser precision,
lies further from it.
"""

from __future__ import annotations

import numpy as np

from harness import reference


def sample(reqs: dict, seed: int, want_tokens: int, max_seqs: int) -> list:
    """uids to check: the longest served request (prompt plus generated
    tokens), then others drawn from ``seed`` until ``want_tokens``
    served tokens or ``max_seqs`` requests are in."""
    served = [u for u, r in reqs.items() if r.generated]
    if not served:
        return []
    served.sort()
    longest = max(served, key=lambda u: (len(reqs[u].prompt)
                                         + len(reqs[u].generated), -u))
    picked = [longest]
    total = len(reqs[longest].generated)
    rng = np.random.default_rng(seed)
    for u in rng.permutation(served):
        if len(picked) >= max_seqs or total >= want_tokens:
            break
        u = int(u)
        if u != longest:
            picked.append(u)
            total += len(reqs[u].generated)
    return picked


def arrays(reqs: dict, logits: dict, uids: list, max_len: int,
           n_seqs: int):
    """Token, read-position, target and program-logit arrays for the
    reference, padded to ``n_seqs`` rows of ``max_len`` (a multiple of
    the reference's query block) so every run reuses one compiled
    program.  ``mask``: served positions; ``lmask``: those whose logits
    the engine handed out (every decoded token after the first, which
    the prefill samples)."""
    qb, vb = reference.QBLOCK, reference.VBLOCK
    S = -(-max_len // qb) * qb
    R = max(len(reqs[u].generated) for u in uids)
    R = -(-R // vb) * vb
    V = next(iter(logits.values()))[0].shape[-1] if logits else 1
    tokens = np.zeros((n_seqs, S), np.int32)
    pos = np.zeros((n_seqs, R), np.int32)
    tgt = np.zeros((n_seqs, R), np.int32)
    mask = np.zeros((n_seqs, R), bool)
    lmask = np.zeros((n_seqs, R), bool)
    prog = np.zeros((n_seqs, R, V), np.float32)
    for j, u in enumerate(uids):
        p, g = reqs[u].prompt, reqs[u].generated
        seq = list(p) + list(g)
        tokens[j, :len(seq)] = seq
        k = len(g)
        # served token i (0-based) is predicted at position len(p)-1+i
        pos[j, :k] = np.arange(len(p) - 1, len(p) - 1 + k)
        tgt[j, :k] = g
        mask[j, :k] = True
        rows = logits.get(u, [])[:k - 1]
        if rows:
            prog[j, 1:1 + len(rows)] = np.stack(rows).astype(np.float32)
            lmask[j, 1:1 + len(rows)] = True
    return tokens, pos, tgt, mask, prog, lmask


def readings(arch, params, d, reqs: dict, logits: dict, uids: list,
             max_len: int, n_seqs: int, control: bool = False) -> dict:
    """The numbers compared (``logit_gap``, ``logit_rel``) and
    ``tokens_checked``, from the reference of the architecture module
    ``arch``; with ``control``, the control's (``control_gap``,
    ``control_rel``) at the same positions, and ``altered_gap``, the
    gap that every served token altered (plus one) would read."""
    import jax.numpy as jnp
    if not uids:
        return {"logit_gap": float("inf"), "logit_rel": float("inf"),
                "tokens_checked": 0}
    tokens, pos, tgt, mask, prog, lmask = arrays(reqs, logits, uids,
                                                 max_len, n_seqs)
    g, cg, rel, crel, alt = (np.asarray(a) for a in reference.readings(
        arch, params, d, jnp.asarray(tokens), jnp.asarray(pos),
        jnp.asarray(tgt), jnp.asarray(prog), control=control))
    out = {"logit_gap": float(g[mask].max()),
           "logit_rel": float(rel[lmask].max()) if lmask.any()
           else float("inf"),
           "tokens_checked": int(mask.sum())}
    if control:
        out["control_gap"] = float(cg[mask].max())
        out["control_rel"] = float(crel[lmask].max()) if lmask.any() \
            else float("inf")
        out["altered_gap"] = float(alt[mask].max())
    return out


def verdict(read: dict, limits: dict) -> tuple:
    """(correct, compared): each compared number beside its limit; the
    two logit numbers are at most theirs, ``tokens_checked`` at least."""
    compared = {k: {"value": read[k], "limit": limits[k]}
                for k in ("logit_gap", "logit_rel", "tokens_checked")}
    ok = (read["logit_gap"] <= limits["logit_gap"]
          and read["logit_rel"] <= limits["logit_rel"]
          and read["tokens_checked"] >= limits["tokens_checked"])
    return ok, compared
