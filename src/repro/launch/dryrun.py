"""Multi-pod dry-run: prove the distribution config is coherent.

For every assigned (architecture x input-shape) cell, on the single-pod
(16, 16) and multi-pod (2, 16, 16) production meshes:

    lowered  = jax.jit(step, in_shardings=..., out_shardings=...)
                   .lower(*input_specs)
    compiled = lowered.compile()
    compiled.memory_analysis()     # proves it fits per-device HBM
    compiled.cost_analysis()       # FLOPs/bytes -> §Roofline
    + collective bytes parsed from the HLO text (all-gather/all-reduce/
      reduce-scatter/all-to-all/collective-permute operand sizes)

No real data is allocated: parameters/optimizer/caches come from
jax.eval_shape; inputs are ShapeDtypeStructs.

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""

import argparse
import functools
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.optim import adamw_init
from repro.serve import engine
from repro.sharding import (logical_to_mesh_axes, param_shardings,
                            set_rules_for_mesh)
from repro.train import step as train_mod

HW = {  # TPU v5e per chip (assignment constants)
    "peak_flops": 197e12,      # bf16
    "hbm_bw": 819e9,           # B/s
    "ici_bw": 50e9,            # B/s/link
    "hbm_bytes": 16 * (1 << 30),
}

_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute")
_TYPE_RE = re.compile(r"(f8e\w+|bf16|f16|f32|f64|u8|u16|u32|u64|s8|s16|"
                      r"s32|s64|pred)\[([0-9,]*)\]")
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "u8": 1,
                "s8": 1, "u16": 2, "s16": 2, "u32": 4, "s32": 4,
                "u64": 8, "s64": 8, "pred": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device output bytes of every collective op in the
    optimized HLO (lines look like
    ``%all-reduce.1 = f32[16,4096]{1,0} all-reduce(...)`` or tuple-typed
    ``= (f32[..], f32[..]) all-reduce(...)``)."""
    out = {op: 0 for op in _OPS}
    out["total"] = 0
    for line in hlo_text.splitlines():
        for op in _OPS:
            marker = f" {op}("
            if marker not in line or "=" not in line:
                continue
            lhs = line.split(marker, 1)[0]
            if "=" not in lhs:
                continue
            types = lhs.split("=", 1)[1]
            for m in _TYPE_RE.finditer(types):
                dt, dims = m.group(1), m.group(2)
                nbytes = _DTYPE_BYTES.get(dt, 1 if dt.startswith("f8")
                                          else 2)
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                out[op] += n * nbytes
                out["total"] += n * nbytes
            break
    return out


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def batch_shardings(batch_sds, mesh):
    def spec(x):
        axes = ("batch",) + (None,) * (len(x.shape) - 1)
        return NamedSharding(mesh, logical_to_mesh_axes(
            axes, mesh=mesh, shape=x.shape))
    return jax.tree.map(spec, batch_sds)


def decode_state_shardings(state_sds, mesh):
    """Cache sharding by tensor role.

    Batch over (pod, data); the cache *sequence* dim over model (kv-head
    counts of the assigned archs — 4/8 — do not divide the 16-way model
    axis, and pjit argument shardings must divide, so the baseline
    shards the 32k/500k-deep time dimension instead; the distributed
    partial-softmax decode of §Perf builds on the same layout).  SSM
    state heads and conv channels shard over model.  All shape-aware.
    """
    def by_path(path, x):
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        nd = len(x.shape)
        if key.endswith("cache_len"):
            return NamedSharding(mesh, P())

        def mk(logical):
            pad = (None,) * (nd - len(logical))
            return NamedSharding(mesh, logical_to_mesh_axes(
                pad + logical, mesh=mesh, shape=x.shape))
        if key.endswith("last_token"):
            return mk(("batch",))
        if key.endswith("/k") or key.endswith("/v"):
            return mk(("batch", None, "seq_kv", None))
        if key.endswith("latent"):
            return mk(("batch", "seq_kv", None))
        if key.endswith("conv"):
            return mk(("batch", None, "inner"))
        if key.endswith("ssm"):
            return mk(("batch", "ssm_heads", None, None))
        return mk(("batch",) + (None,) * (nd - 1))
    return jax.tree_util.tree_map_with_path(by_path, state_sds)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------

def lower_cell(arch: str, shape_name: str, mesh, *,
               moment_dtype: str = "bfloat16"):
    """Returns (lowered, meta) for one assignment cell."""
    cfg = configs.get_config(arch)
    sh = configs.SHAPES[shape_name]
    specs = configs.input_specs(arch, shape_name, cfg)
    params_sds, axes = tf.abstract_params(cfg)

    with set_rules_for_mesh(mesh):
        p_shard = param_shardings(axes, mesh, like=params_sds)

        if sh.kind == "train":
            opt_sds = jax.eval_shape(
                functools.partial(adamw_init, moment_dtype=moment_dtype),
                params_sds)
            state_sds = train_mod.TrainState(params=params_sds,
                                             opt=opt_sds, feedback=None)
            opt_shard = train_mod.TrainState(
                params=p_shard,
                opt=type(opt_sds)(
                    step=NamedSharding(mesh, P()),
                    mu=jax.tree.map(lambda s: s, p_shard),
                    nu=jax.tree.map(lambda s: s, p_shard)),
                feedback=None)
            b_shard = batch_shardings(specs["batch"], mesh)

            def step(state, batch):
                return train_mod.train_step(state, batch, cfg,
                                            lr=1e-4, microbatches=1)

            jitted = jax.jit(step,
                             in_shardings=(opt_shard, b_shard),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, specs["batch"])

        elif sh.kind == "prefill":
            if arch in configs.ENCODER_ONLY:
                def enc(params, embeds):
                    return tf.forward(params, cfg, embeds=embeds)
                jitted = jax.jit(enc, in_shardings=(
                    p_shard, batch_shardings(specs["embeds"], mesh)))
                lowered = jitted.lower(params_sds, specs["embeds"])
            else:
                state_sds = jax.eval_shape(
                    lambda: engine.init_decode_state(
                        cfg, sh.global_batch, sh.seq_len,
                        jnp.dtype(cfg.compute_dtype)))
                s_shard = decode_state_shardings(state_sds, mesh)
                tok_sds = specs["tokens"]

                def pre(params, tokens, state):
                    return engine.prefill(params, cfg, tokens, state)

                jitted = jax.jit(
                    pre,
                    in_shardings=(p_shard,
                                  batch_shardings(tok_sds, mesh),
                                  s_shard),
                    out_shardings=s_shard,
                    donate_argnums=(2,))
                lowered = jitted.lower(params_sds, tok_sds, state_sds)

        else:  # decode
            state_sds = jax.eval_shape(
                lambda: engine.init_decode_state(
                    cfg, specs["batch"], specs["max_len"],
                    jnp.dtype(cfg.compute_dtype)))
            # dry-run semantics: cache_len is a filled prefix
            s_shard = decode_state_shardings(state_sds, mesh)

            def dec(params, state):
                return engine.serve_step(params, cfg, state)

            jitted = jax.jit(dec, in_shardings=(p_shard, s_shard),
                             out_shardings=s_shard, donate_argnums=(1,))
            lowered = jitted.lower(params_sds, state_sds)

    import math
    n_params = sum(math.prod(l.shape) if l.shape else 1
                   for l in jax.tree.leaves(params_sds))
    return lowered, {"arch": arch, "shape": shape_name,
                     "kind": sh.kind, "n_params": n_params}


def analyse(lowered, meta, mesh) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0
    n_dev = mesh.devices.size
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    out = dict(meta)
    out.update({
        "devices": int(n_dev),
        "compile_seconds": round(compile_s, 1),
        "per_device": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes",
                                          0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)
                              or 0),
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "collective_bytes": coll,
        },
        "roofline_seconds": {
            "compute": flops / HW["peak_flops"],
            "memory": bytes_acc / HW["hbm_bw"],
            "collective": coll["total"] / HW["ici_bw"],
        },
    })
    rt = out["roofline_seconds"]
    out["bottleneck"] = max(rt, key=rt.get)
    live = out["per_device"]["argument_bytes"] \
        + out["per_device"]["temp_bytes"]
    peak = out["per_device"]["peak_bytes"] or live
    out["fits_hbm"] = bool(min(live, peak) <= HW["hbm_bytes"])
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             moment_dtype: str = "bfloat16") -> dict:
    ok, why = configs.applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    lowered, meta = lower_cell(arch, shape_name, mesh,
                               moment_dtype=moment_dtype)
    return analyse(lowered, meta, mesh)


# ---------------------------------------------------------------------------
# Scan-corrected cost analysis (the roofline numbers)
#
# XLA's cost_analysis counts a while(=lax.scan) body ONCE, regardless of
# trip count, so a 40-layer scanned model reports ~1/40th of its real
# FLOPs/bytes/collectives.  We therefore lower the SAME cell at scan
# depths of 1 and 2 periods and extrapolate linearly:
#     per-trip cost = C(2) - C(1);   total = C(1) + (trips-1) * (C2-C1)
# which is exact for a homogeneous scan body (every trip executes the
# same HLO).  The full-depth compile (run_cell) remains the memory-fit
# and compile-coherence proof; this probe supplies the cost terms.
# ---------------------------------------------------------------------------

def _depth_config(cfg, periods: int):
    import dataclasses as _dc
    return _dc.replace(
        cfg, scan_layers=False,
        n_layers=cfg.first_dense_layers + periods * cfg.layer_period)


def lower_cell_cfg(cfg, arch, shape_name, mesh, *,
                   moment_dtype: str = "bfloat16", rules=None):
    """lower_cell with an explicit (possibly depth-reduced) config."""
    sh = configs.SHAPES[shape_name]
    specs = configs.input_specs(arch, shape_name, cfg)
    params_sds, axes = tf.abstract_params(cfg)

    with set_rules_for_mesh(mesh, rules):
        p_shard = param_shardings(axes, mesh, like=params_sds)
        if sh.kind == "train":
            opt_sds = jax.eval_shape(
                functools.partial(adamw_init, moment_dtype=moment_dtype),
                params_sds)
            state_sds = train_mod.TrainState(params=params_sds,
                                             opt=opt_sds, feedback=None)
            opt_shard = train_mod.TrainState(
                params=p_shard,
                opt=type(opt_sds)(
                    step=NamedSharding(mesh, P()),
                    mu=jax.tree.map(lambda s: s, p_shard),
                    nu=jax.tree.map(lambda s: s, p_shard)),
                feedback=None)
            b_shard = batch_shardings(specs["batch"], mesh)

            def step(state, batch):
                return train_mod.train_step(state, batch, cfg,
                                            lr=1e-4, microbatches=1)

            return jax.jit(step, in_shardings=(opt_shard, b_shard),
                           donate_argnums=(0,)) \
                .lower(state_sds, specs["batch"])
        if sh.kind == "prefill":
            if arch in configs.ENCODER_ONLY:
                def enc(params, embeds):
                    return tf.forward(params, cfg, embeds=embeds)
                return jax.jit(enc, in_shardings=(
                    p_shard, batch_shardings(specs["embeds"], mesh))) \
                    .lower(params_sds, specs["embeds"])
            state_sds = jax.eval_shape(
                lambda: engine.init_decode_state(
                    cfg, sh.global_batch, sh.seq_len,
                    jnp.dtype(cfg.compute_dtype)))
            s_shard = decode_state_shardings(state_sds, mesh)

            def pre(params, tokens, state):
                return engine.prefill(params, cfg, tokens, state)

            return jax.jit(pre, in_shardings=(
                p_shard, batch_shardings(specs["tokens"], mesh),
                s_shard), out_shardings=s_shard, donate_argnums=(2,)) \
                .lower(params_sds, specs["tokens"], state_sds)
        state_sds = jax.eval_shape(
            lambda: engine.init_decode_state(
                cfg, specs["batch"], specs["max_len"],
                jnp.dtype(cfg.compute_dtype)))
        s_shard = decode_state_shardings(state_sds, mesh)

        def dec(params, state):
            return engine.serve_step(params, cfg, state)

        return jax.jit(dec, in_shardings=(p_shard, s_shard),
                       out_shardings=s_shard, donate_argnums=(1,)) \
            .lower(params_sds, state_sds)


def _cost_triple(lowered):
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll)


def roofline_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                  moment_dtype: str = "bfloat16",
                  cfg_override=None, rules=None) -> dict:
    """Scan-corrected roofline terms for one cell."""
    ok, why = configs.applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    base = cfg_override or configs.get_config(arch)
    trips = base.n_periods
    c1 = _cost_triple(lower_cell_cfg(_depth_config(base, 1), arch,
                                     shape_name, mesh,
                                     moment_dtype=moment_dtype,
                                     rules=rules))
    c2 = _cost_triple(lower_cell_cfg(_depth_config(base, 2), arch,
                                     shape_name, mesh,
                                     moment_dtype=moment_dtype,
                                     rules=rules))

    def extrap(a, b):
        return a + (trips - 1) * max(b - a, 0.0)

    flops = extrap(c1[0], c2[0])
    bytes_acc = extrap(c1[1], c2[1])
    coll = {k: extrap(c1[2][k], c2[2][k]) for k in c1[2]}
    out = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": int(mesh.devices.size),
        "scan_trips": trips,
        "per_device": {"flops": flops, "bytes_accessed": bytes_acc,
                       "collective_bytes": coll},
        "roofline_seconds": {
            "compute": flops / HW["peak_flops"],
            "memory": bytes_acc / HW["hbm_bw"],
            "collective": coll["total"] / HW["ici_bw"],
        },
    }
    rt = out["roofline_seconds"]
    out["bottleneck"] = max(rt, key=rt.get)
    return out


def main(argv=None):
    # 512 host devices stand in for the production meshes; read when the
    # backend first initialises, which nothing before this point does
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="scan-corrected cost probe (1- and 2-period "
                         "lowerings, linear extrapolation) instead of "
                         "the full-depth compile")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        cells = [(a, s) for a, s, ok, _ in configs.cells() ]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                if args.roofline:
                    r = roofline_cell(arch, shape, multi_pod=mp)
                else:
                    r = run_cell(arch, shape, multi_pod=mp)
                r["mesh"] = "2x16x16" if mp else "16x16"
                results.append(r)
                if "skipped" in r:
                    print(f"[skip] {tag}: {r['skipped']}", flush=True)
                else:
                    rt = r["roofline_seconds"]
                    extra = f"compile {r['compile_seconds']}s " \
                        if "compile_seconds" in r else \
                        f"trips {r.get('scan_trips')} "
                    fits = f" fits={r['fits_hbm']}" \
                        if "fits_hbm" in r else ""
                    print(f"[ok]   {tag}: {extra}"
                          f"flops/dev {r['per_device']['flops']:.3e} "
                          f"bottleneck {r['bottleneck']} "
                          f"(c={rt['compute']:.4f}s m={rt['memory']:.4f}s "
                          f"n={rt['collective']:.4f}s){fits}", flush=True)
            except Exception as e:  # report, keep going
                results.append({"arch": arch, "shape": shape,
                                "mesh": "2x16x16" if mp else "16x16",
                                "error": f"{type(e).__name__}: {e}"})
                print(f"[FAIL] {tag}: {type(e).__name__}: "
                      f"{str(e)[:300]}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    nfail = sum(1 for r in results if "error" in r)
    return 1 if nfail else 0


if __name__ == "__main__":
    sys.exit(main())
