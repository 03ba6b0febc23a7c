"""A configuration, a cell, a per-layer metric and a kernel count are
each added to a temporary copy of the benchmark as a new file and a
manifest entry, with no edit to a file that was there, and the harness
picks each of them up by name."""

import json

from conftest import ROOT, make_tiny_root
from harness import cell, counts
from harness.manifest import Manifest
from harness.recorder import Span

NEW_METRIC = '''"""Decode steps in the window (a count)."""


def read(run):
    return float(sum(1 for s in run.spans if s.kind == "decode"))
'''

NEW_KERNEL = '''"""A made-up decode kernel for the path "made_up_path"."""

EVENT = r" custom-call:made_up$"
PHASE = "decode"
PATH = "made_up_path"


def cost(d, span):
    return 1.0e3, 1.0e3
'''


def test_add_files_and_entries_only(tmp_path):
    before = {p.relative_to(ROOT): p.read_bytes()
              for p in (ROOT / "bench").rglob("*") if p.is_file()
              and "__pycache__" not in p.parts}
    # the tiny configuration and cell come in as new files and entries
    root = make_tiny_root(tmp_path)
    (root / "bench" / "metrics" / "decode_steps.py").write_text(NEW_METRIC)
    (root / "bench" / "kernels" / "made_up_kernel.py").write_text(NEW_KERNEL)
    man_path = root / "BENCHMARK.json"
    man = json.loads(man_path.read_text())
    man["per_layer"].append({
        "name": "decode_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "model step",
        "moves": "itl_p95_ms", "workloads": ["tiny.mix"]})
    man_path.write_text(json.dumps(man))
    for rel, data in before.items():
        if rel.name != "peaks.json":        # the CPU's peaks: test only
            assert (root / rel).read_bytes() == data, rel

    m = Manifest(root)
    out = cell.run(m, "tiny.mix", 3, 1.0, True, 0.0, compile_cache=False)
    assert out["correct"] is True
    assert out["metrics"]["decode_steps"]["value"] > 0
    assert {"mfu.decode", "scheduler_ms"} <= set(out["metrics"])

    made_up = [k for k in m.kernels() if k.PATH == "made_up_path"]
    assert len(made_up) == 1

    class Run:
        spans = [Span("decode", 0, 1, "made_up_path", rows=1,
                      contexts=(4,))]
        events = {"host": [["window", 0, 100], ["decode_step", 0, 100]],
                  "device": [["k.1 custom-call:made_up", 0, 50]]}
        arch = m.arch("gqa")
        dims = arch.dims(m.config("tiny"))
        peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e12}
        kernels = m.kernels()
        notes: list = []
    # 2 layers x max(1e3 / 1e12, 1e3 / 1e12) s over 50 ns of kernel time
    assert abs(counts.roofline(Run, "decode") - 100 * 2e-9 / 50e-9) < 1e-9
