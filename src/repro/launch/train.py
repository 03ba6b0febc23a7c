"""Training driver: data pipeline -> pjit train step -> checkpoint ->
restart harness.  Runs any --arch at any scale the local device set
allows (full configs are exercised compile-only via dryrun.py; this
driver trains the reduced/smoke configs end-to-end on CPU and the full
ones on a real slice).

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen3-8b --smoke --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticTokenDataset, make_batch_iterator
from repro.launch.compilation import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import cosine_schedule
from repro.runtime import StepTimer
from repro.sharding import param_shardings, set_rules_for_mesh
from repro.train import step as train_mod


def build(cfg, *, batch: int, seq: int, lr: float, steps: int,
          mesh=None, moment_dtype="float32", grad_compression=False,
          microbatches=1, seed=0, structured_data=True):
    """Returns (state, jitted step_fn, dataset)."""
    state, axes = train_mod.init_train_state(
        jax.random.PRNGKey(seed), cfg, moment_dtype=moment_dtype,
        grad_compression=grad_compression)
    sched = cosine_schedule(lr, warmup_steps=max(steps // 20, 1),
                            total_steps=steps)
    step_fn = functools.partial(train_mod.train_step, cfg=cfg, lr=sched,
                                microbatches=microbatches)
    if mesh is not None:
        with set_rules_for_mesh(mesh):
            p_sh = param_shardings(axes, mesh, like=state.params)
            state = train_mod.TrainState(
                params=jax.tree.map(jax.device_put, state.params, p_sh),
                opt=state.opt, feedback=state.feedback)
    jitted = jax.jit(step_fn, donate_argnums=(0,))
    ds = SyntheticTokenDataset(cfg.vocab_size, seq, batch, seed=seed,
                               structured=structured_data)
    return state, jitted, ds, axes


def train_loop(cfg, *, steps: int, batch: int, seq: int, lr: float,
               ckpt_dir=None, checkpoint_every=50, mesh=None,
               log_every=10, **kw):
    state, jitted, ds, axes = build(cfg, batch=batch, seq=seq, lr=lr,
                                    steps=steps, mesh=mesh, **kw)
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if ckpt and ckpt.latest_step() is not None:
        state, extras = ckpt.restore(state)
        start = extras["next_step"]
        print(f"resumed from step {start}")
    timer = StepTimer()
    it = make_batch_iterator(ds, start_step=start)
    losses = []
    ctx = set_rules_for_mesh(mesh) if mesh is not None else _null()
    with ctx:
        for step, rows in it:
            if step >= steps:
                break
            timer.start()
            batch_tree = {"tokens": jnp.asarray(rows)}
            state, metrics = jitted(state, batch_tree)
            straggler = timer.stop()
            loss = float(metrics["loss"])
            losses.append(loss)
            if step % log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}"
                      + (" [straggler]" if straggler else ""),
                      flush=True)
            if ckpt and (step + 1) % checkpoint_every == 0:
                ckpt.save(step, state, extras={"next_step": step + 1})
        it.close()
    if ckpt:
        ckpt.wait()
    return state, losses


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b",
                    choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", action="store_true",
                    help="use a host mesh (data x model over devices)")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(data=len(jax.devices())) if args.mesh else None
    t0 = time.time()
    _, losses = train_loop(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, lr=args.lr,
                           ckpt_dir=args.ckpt_dir, mesh=mesh,
                           microbatches=args.microbatches)
    print(f"done: {len(losses)} steps in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
