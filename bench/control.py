"""Read the numbers ``correct`` compares, for the program and for its
control, on the chip at a cell's own size: several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 51]

For each seed: weights from the seed, the cell's set-up and its window at
the cell's own load (as ``run.py`` does), then, once the program's state
is freed, the plain float32 reference over the sampled requests' prompts
and served tokens.  Each row gives the program's two compared numbers
(the widest logit gap of a served token, the largest relative distance
of its logits) and the control's: the same reference with every tensor
the program holds in bf16 held in fp8 (the architecture module's
``final_hidden`` with ``lowp``, ``bench/arch/<arch>.py``), teacher-forced on the same tokens, read at the same positions.  The
benchmark's runs never run the control; these rows set the cell's
limits.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    from harness.manifest import Manifest
    man = Manifest()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    from harness.cell import Session, say
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ses = Session(man, args.workload, seed)
        s = ses.serve(ses.plan(seed), args.seconds)
        ses.report(s)
        reqs, logits = s["driver"].reqs, ses.engine.logits
        del s
        ses.free_engine()
        read = ses.check(reqs, logits, control=True)
        row = dict(seed=seed, **read)
        say("control row " + json.dumps(row))
        rows.append(row)
        del ses, reqs
        gc.collect()
    print(json.dumps({"rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
