"""Per read position, the numbers ``correct`` compares, for the program
and for its fp8 control, beside the reference's router margins, on a
cell of the ``mla_moe`` architecture at its own size:

    python3 bench/mla_moe_readings.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 30] [--out DIR]

For each seed, as ``bench/control.py`` does: the cell's set-up and its
window, the engine's state freed, and the sample of served requests the
cell's check draws.  The reference's and the control's hidden states
are computed with every weight on the device; then every weight but the
logits head is dropped, so that the head's two float32 copies the
control's logits take fit beside it (beside all the weights of a 9 GiB
model they do not, on a 16 GiB chip).

A router choice is discontinuous: where the 8th and 9th experts' biased
scores, or the 4th and 5th groups' scores, lie within rounding of each
other, the bf16 program and the float32 reference may choose
differently, and a held expert's share then moves that position's
logits.  The reference takes the program's choice there (the
architecture's module doc); each row counts the positions where it did
(``took``) and where the program's choice was too far off to take
(``refused``), and splits the program's relative distance between the
read positions within 2 ``TIE`` of such a tie in some MoE layer and the
rest.  With ``--out``, every position's numbers are
written to ``DIR/<cell>_<seed>.json``.  Nothing here decides a run's
``correct``; the rows set the cell's limits.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def readings(ses, reqs: dict, logits: dict) -> dict:
    """Every read position's numbers for one served window; frees the
    session's weights (all but the head, then the head)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import check, reference
    chk = ses.mix["check"]
    n_seqs = int(chk["max_sequences"])
    uids = check.sample(reqs, ses.seed, int(chk["sample_tokens"]), n_seqs)
    tokens, pos, tgt, mask, prog, lmask = check.arrays(
        reqs, logits, uids, ses.max_len, n_seqs)
    arch, d = ses.arch, ses.dims
    h_ref, margins = arch.hidden_and_margins(
        ses.params, d, jnp.asarray(tokens), jnp.asarray(pos),
        routes=arch.program_routes(d, tokens, pos))
    h_ctrl = arch.final_hidden(ses.params, d, jnp.asarray(tokens),
                               jnp.asarray(pos), lowp=True)
    jax.block_until_ready((h_ref, h_ctrl))
    head = ses.params["lm_head"]
    ses.params = None
    gc.collect()
    gap, cgap, rel, crel, _ = (np.asarray(a) for a in reference._readings(
        True)(head, h_ref, jnp.asarray(tgt), h_ctrl, jnp.asarray(prog)))
    del head
    return {"uids": uids, "pos": pos, "mask": mask, "lmask": lmask,
            "gap": gap, "ctrl_gap": cgap, "rel": rel, "ctrl_rel": crel,
            "margins": np.asarray(margins)}


def summary(r: dict, tie: float, window: int) -> dict:
    """The compared numbers of the program and the control, and the
    program's relative distance split by closeness to a router tie."""
    import numpy as np
    m, lm = r["mask"], r["lmask"]
    # (n, R, 2): the smallest margin over MoE layers, expert and group,
    # and whether the program's choice was taken / refused in some layer
    least = r["margins"][..., :2].min(-2)
    took, refused = (r["margins"][..., 2:] > 0).any(-2).transpose(2, 0, 1)
    near = lm & (least < 2 * tie).any(-1)
    far = lm & ~near
    rel = r["rel"]

    def top(sel):
        return float(rel[sel].max()) if sel.any() else None

    order = np.argsort(-np.where(lm, rel, -1.0), axis=None)[:10]
    worst = []
    for k in order:
        j, i = np.unravel_index(k, rel.shape)
        if lm[j, i]:
            worst.append({"pos": int(r["pos"][j, i]),
                          "rel": float(rel[j, i]),
                          "expert_margin": float(least[j, i, 0]),
                          "group_margin": float(least[j, i, 1]),
                          "took": bool(took[j, i])})
    return {
        "logit_gap": float(r["gap"][m].max()),
        "logit_rel": float(rel[lm].max()),
        "control_gap": float(r["ctrl_gap"][m].max()),
        "control_rel": float(r["ctrl_rel"][lm].max()),
        "control_rel_min": float(r["ctrl_rel"][lm].min()),
        "positions": int(lm.sum()),
        "near_tie": int(near.sum()),
        "took": int((lm & took).sum()),
        "refused": int((lm & refused).sum()),
        "rel_near_tie_max": top(near),
        "rel_far_max": top(far),
        "rel_far_past_window_max": top(far & (r["pos"] >= window)),
        "rel_median": float(np.median(rel[lm])),
        "rel_over_0.02": int((lm & (rel > 0.02)).sum()),
        "worst": worst,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None, metavar="DIR")
    args = ap.parse_args(argv)

    from harness.manifest import Manifest
    from harness.cell import Session, say
    man = Manifest()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ses = Session(man, args.workload, seed)
        if not hasattr(ses.arch, "hidden_and_margins"):
            print(f"mla_moe_readings.py: {args.workload}'s architecture "
                  f"gives no router margins", file=sys.stderr)
            return 2
        s = ses.serve(ses.plan(seed), args.seconds)
        ses.report(s)
        say(f"peak_bytes_in_use "
            f"{(ses.dev.memory_stats() or {}).get('peak_bytes_in_use')}")
        reqs, logits = s["driver"].reqs, ses.engine.logits
        del s
        ses.free_engine()
        r = readings(ses, reqs, logits)
        row = dict(seed=seed, **summary(r, ses.arch.TIE,
                                        ses.dims.yarn_original))
        say("readings row " + json.dumps(row))
        rows.append(row)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            path = os.path.join(args.out, f"{args.workload}_{seed}.json")
            with open(path, "w") as f:
                json.dump({k: v.tolist() if hasattr(v, "tolist") else v
                           for k, v in r.items()}, f)
        del ses, reqs, logits, r
        gc.collect()
    print(json.dumps({"rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
