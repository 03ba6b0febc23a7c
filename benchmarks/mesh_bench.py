"""§Mesh lowering: heterogeneous DSE search + 2-device mesh execution.

Two kinds of rows:

* deterministic DSE rows (stable regression signal, no jax timing
  noise): the heterogeneous GA's found fitness and softmax-offload
  count on the canonical 1 PE-array + 1 SIMD-heavy platform, and the
  engine-predicted ``comm_cycles`` of head-partitioned multi-core
  schedules (round-robin vs skewed vs single-core) — the numbers
  ``tools/validate_costmodel.py --mesh`` validates against measured
  collectives;
* measured mesh rows (informational, ``_us`` fields): the wall-time of
  the output-partial psum the lowered head-parallel serve executes,
  plus one full ``head_parallel_decode_attention`` step, on a
  2-device mesh — in-process when two devices exist, else (on the
  CPU only) in a child forced onto two host devices.
"""

import json
import os
import subprocess
import sys

from repro.core import accelerator as acc
from repro.core import allocation as galloc
from repro.core import scheduler as sch
from repro.core import workload as wl


def _measure_rows() -> list:
    """Wall-time the output-partial psum and one head-parallel decode
    step on a 2-device mesh of this process's devices."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh_lowering import mesh_for_cores
    from repro.serve.distributed_decode import \
        head_parallel_decode_attention
    from repro.sharding import set_rules_for_mesh

    def measure_us(fn, args, repeats=5):
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(*args))
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    mesh = mesh_for_cores(2)
    rows = []
    for M, E in ((64, 256), (128, 512)):
        fn = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                           in_specs=P("model", None, None),
                           out_specs=P(None, None, None), check_vma=False)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, M, E),
                              jnp.float32)
        rows.append({"name": f"mesh_psum_M{M}_E{E}",
                     "collective_us": round(measure_us(fn, (x,)), 1)})

    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, 4, 1, 32))
    k = jax.random.normal(ks[1], (2, 2, 64, 32))
    v = jax.random.normal(ks[2], (2, 2, 64, 32))
    wo = jax.random.normal(ks[3], (4, 32, 128)) * 0.1
    lengths = jnp.array([64, 17])
    with set_rules_for_mesh(mesh):
        us = measure_us(lambda *a: head_parallel_decode_attention(*a),
                        (q, k, v, lengths, wo))
    rows.append({"name": "mesh_head_parallel_step",
                 "step_us": round(us, 1)})
    return rows


def _dse_rows() -> list:
    rows = []
    hetero = acc.hetero_platform(1, 1)
    ga = galloc.optimize_allocation(64, 16, 2, hetero, generations=6,
                                    population=8, seed=0)
    all_pe = sch.evaluate(wl.parallel_heads(64, 16, 2), hetero,
                          galloc.heads_schedule(64, 16, (0, 0)),
                          row_block=1)
    rows.append({
        "name": "hetero_ga_softmax_offload",
        "platform": hetero.name,
        "allocation": list(ga.allocation),
        "softmax_allocation": list(ga.softmax_allocation),
        "offloaded_heads": sum(
            1 for c, s in zip(ga.allocation, ga.softmax_allocation)
            if s != c),
        "fitness_cycles": ga.fitness,
        "all_pe_cycles": all_pe.latency_cycles,
        "speedup_vs_all_pe": round(all_pe.latency_cycles / ga.fitness, 2),
        "evaluations": ga.evaluations,
    })
    accel = acc.multi_core_array(2)
    for label, allocation in (("rr", (0, 1, 0, 1)),
                              ("skew", (0, 0, 0, 1)),
                              ("single", (0, 0, 0, 0))):
        workload, schedule = galloc.head_partition_schedule(
            64, 256, 4, 64, allocation)
        res = sch.evaluate(workload, accel, schedule, row_block=1)
        rows.append({
            "name": f"head_partition_comm_{label}",
            "allocation": list(allocation),
            "comm_cycles": res.comm_cycles,
            "latency_cycles": res.latency_cycles,
        })
    return rows


def _mesh_rows() -> list:
    """The measured rows: in this process when it already has two
    devices; on a TPU host with one chip, refused (a child process could
    not reach the chip this process holds); on the CPU, in a child
    forced onto two host devices."""
    import jax
    if jax.device_count() >= 2:
        return _measure_rows()
    if jax.default_backend() == "tpu":
        return [{"name": "mesh_measured_skipped",
                 "reason": "one TPU device: the 2-device mesh rows need "
                           "two chips in this process"}]
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(root, "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if "PYTHONPATH" in os.environ else [])))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.mesh_bench", "--measure"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        return [{"name": "mesh_measured_skipped",
                 "reason": out.stderr[-500:]}]
    return json.loads(out.stdout.strip().splitlines()[-1])


def run() -> list:
    return _dse_rows() + _mesh_rows()


if __name__ == "__main__":
    if "--measure" in sys.argv:
        print(json.dumps(_measure_rows()))
    else:
        for row in run():
            print(row)
