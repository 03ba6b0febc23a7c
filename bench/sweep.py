"""Find the knee of an open-loop cell on the chip, in one process with one
set-up.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 0.1,0.2 \
        [--seconds 51]

For each mean rate the engine is emptied, the cell's steady-state rows
are filled again, and the cell's traffic is offered at that rate for the
window, with the mix's own arrival process (Poisson, or the Gamma gaps
its ``arrivals`` names: the same draws, scaled to the rate).  A row is
printed per rate: requests due and finished, tokens/s,
the TTFT median and 90th percentile, the gap 95th percentile, the number
of requests waiting for a slot at the start and the end of the window
and its least-squares slope, and the mean scheduler step with at least
three quarters of the rows live.

Two readings of the knee come out.  ``knee_queue``: the highest rate
whose queue does not grow over the window (slope under one request per
window, and at most one more waiting at the end than at the start).
``knee_capacity``: the batch over that mean step, over the steps a
request holds its row (its prefill chunks, one a step, and its output
tokens): the rate at which every row would be busy.  Where a request
holds its row longer than the window, the queue cannot grow inside it
and only the second reading binds; the knee is the smaller of the two.
With ``--write`` the cell's traffic file gets 0.8 x the knee as its
``rate``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def slope(points) -> float:
    """Least-squares slope of (t, n) points, per second."""
    if len(points) < 2:
        return 0.0
    ts = [t for t, _ in points]
    ns = [n for _, n in points]
    mt, mn = sum(ts) / len(ts), sum(ns) / len(ns)
    var = sum((t - mt) ** 2 for t in ts)
    return sum((t - mt) * (n - mn) for t, n in points) / var if var else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated mean rates, requests/s")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--write", action="store_true",
                    help="write 0.8 x the knee into the traffic file")
    args = ap.parse_args(argv)

    from harness.manifest import Manifest
    man = Manifest()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    from harness import stats
    from harness.cell import Session, say
    ses = Session(man, args.workload, args.seed)
    if ses.mix["loop"] != "open":
        print("sweep.py: the cell is not open-loop", file=sys.stderr)
        return 2
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(ses.mix, rate=rate)
        ses.reset()
        s = ses.serve(ses.plan(args.seed, mix), args.seconds)
        ses.report(s)
        tl, drv = s["timeline"], s["driver"]
        q = [(t, n) for t, n, _ in drv.queue_len if tl.t0 <= t <= tl.t1]
        ends = [(t, live) for t, _, live in drv.queue_len
                if tl.t0 <= t <= tl.t1]
        busy = [b[0] - a[0] for a, b in zip(ends, ends[1:])
                if 4 * b[1] >= 3 * ses.engine.batch_size]
        tt = stats.ttfts(tl)
        g = stats.gaps(tl)
        due = stats.due_in_window(tl)
        row = {
            "rate": rate, "due": len(due),
            "finished": sum(1 for u in due if drv.reqs[u].done),
            "tokens_per_s": stats.tokens_in_window(tl) / tl.seconds,
            "ttft_p50_s": stats.percentile(tt, 50),
            "ttft_p90_s": stats.percentile(tt, 90),
            "itl_p95_s": stats.percentile(g, 95),
            "waiting_start": q[0][1] if q else 0,
            "waiting_end": q[-1][1] if q else 0,
            "waiting_slope_per_window": slope(q) * tl.seconds,
            "steps": tl.steps - s["fill_steps"],
            "busy_step_s": sum(busy) / len(busy) if busy else None,
            "window_s": tl.seconds,
        }
        row["grows"] = (row["waiting_slope_per_window"] >= 1.0
                        or row["waiting_end"] > row["waiting_start"] + 1)
        say("sweep row " + json.dumps(row))
        rows.append(row)
    ok = [r["rate"] for r in rows if not r["grows"]]
    knee_queue = max(ok) if ok else None
    steps = [r["busy_step_s"] for r in rows if r["busy_step_s"]]
    stream = ses.plan(args.seed).stream
    chunk = ses.engine.prefill_chunk
    held = sum(r.max_new + -(-len(r.prompt) // chunk)
               for r in stream) / len(stream)
    knee_capacity = (ses.engine.batch_size / (sum(steps) / len(steps))
                     / held) if steps else None
    knee = min(k for k in (knee_queue, knee_capacity) if k is not None)
    say(f"knee_queue {knee_queue} requests/s, knee_capacity "
        f"{knee_capacity} requests/s (a row held {held:.1f} steps); "
        f"knee {knee}, 0.8 x knee = {0.8 * knee}")
    if args.write:
        path = man.bench / "traffic" / f"{ses.cell['traffic']}.json"
        mix = json.loads(path.read_text())
        mix["rate"] = float(f"{0.8 * knee:.3g}")
        path.write_text(json.dumps(mix, indent=2) + "\n")
        say(f"rate {mix['rate']} written to {path}")
    print(json.dumps({"rows": rows, "knee_queue": knee_queue,
                      "knee_capacity": knee_capacity, "knee": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
