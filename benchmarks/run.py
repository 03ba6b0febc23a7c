"""Benchmark harness: one module per paper table/figure + the roofline
table + the engine/block-exploration benches.  Prints
``name,us_per_call,derived`` CSV lines per the repo contract plus a
readable report, and drops one machine-readable ``BENCH_<area>.json``
per module run (rows verbatim — config/shape fields, wall-clock,
tokens/s, kernel path, lengths_downgrades as each module reports them)
so dashboards and regression diffs never re-parse the CSV.

    PYTHONPATH=src python -m benchmarks.run                 # everything
    PYTHONPATH=src python -m benchmarks.run --only fig6_alpha
    PYTHONPATH=src python -m benchmarks.run --only blocks_bench --only roofline

``--only`` takes a module name (repeatable) and skips importing the
unselected modules, so e.g. the pure-DSE figures run without JAX.
``--outdir`` relocates the JSON artifacts (default: cwd).
"""

import argparse
import importlib
import json
import pathlib
import time

# module name -> import path, in report order
MODULES = {
    "fig4_validation": "benchmarks.fig4_validation",
    "fig5_memory_traces": "benchmarks.fig5_memory_traces",
    "fig6_alpha": "benchmarks.fig6_alpha",
    "tableI_features": "benchmarks.tableI_features",
    "engine_bench": "benchmarks.engine_bench",
    "blocks_bench": "benchmarks.blocks_bench",
    "phase_sweep": "benchmarks.phase_sweep",
    "lowering_bench": "benchmarks.lowering_bench",
    "mesh_bench": "benchmarks.mesh_bench",
    "kernel_bench": "benchmarks.kernel_bench",
    "roofline": "benchmarks.roofline",
}

# module name -> JSON artifact area (default: the module name itself)
AREAS = {"kernel_bench": "kernels", "engine_bench": "engine",
         "blocks_bench": "blocks", "lowering_bench": "lowering",
         "mesh_bench": "mesh"}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--only", action="append", choices=sorted(MODULES),
                        metavar="FIGURE",
                        help="run only this module (repeatable); "
                             f"one of: {', '.join(MODULES)}")
    parser.add_argument("--outdir", default=".",
                        help="directory for the BENCH_<area>.json "
                             "artifacts (default: cwd)")
    args = parser.parse_args(argv)
    from repro.launch.compilation import enable_compile_cache
    enable_compile_cache()
    selected = args.only or list(MODULES)
    outdir = pathlib.Path(args.outdir)
    outdir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for name in MODULES:
        if name not in selected:
            continue
        mod = importlib.import_module(MODULES[name])
        t0 = time.perf_counter()
        rows = mod.run()
        us = (time.perf_counter() - t0) * 1e6 / max(len(rows), 1)
        area = AREAS.get(name, name)
        artifact = {"bench": name, "area": area,
                    "us_per_row": round(us, 1), "rows": rows}
        (outdir / f"BENCH_{area}.json").write_text(
            json.dumps(artifact, indent=2, default=str) + "\n")
        for r in rows:
            rname = r.pop("name")
            print(f"{rname},{us:.0f},\"{json.dumps(r)}\"")


if __name__ == "__main__":
    main()
