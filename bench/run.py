"""Run one cell of the serving benchmark on the chip and print its line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json``.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero before printing any result.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared with its limit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def process_start() -> float:
    """Epoch seconds at which this process started (set-up counts from
    here), from /proc; the time of this call where /proc is absent."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return time.time()


def main(argv=None) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-events", default=None, metavar="PATH",
                    help="with --trace 1, also write the trace's plain "
                         "events (harness/trace.py) to PATH as JSON")
    args = ap.parse_args(argv)

    from harness.manifest import Manifest
    man = Manifest()
    cell = man.cell(args.workload)

    import jax
    devs = jax.devices()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < int(cell["chips"]):
        print(f"run.py: the cell asks for {cell['chips']} chips, JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    from harness import cell as runner
    out = runner.run(man, args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    events = out.pop("_events", None)
    if args.dump_events and events is not None:
        with open(args.dump_events, "w") as f:
            json.dump(events, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
