"""Chip smoke: serve starcoder2-7b at its published widths on a TPU.

One chip (the default):

* 16 of the model's 32 layers (one stage of a 2-stage pipeline
  deployment); every width, head count, the vocabulary and bf16 as
  published; random weights from ``--seed``;
* six requests (prompts of 256, 512 and 768 tokens, 64 new tokens
  each) through ``launch/serve.py``'s engine and ``RequestBatcher`` on
  the paged engine: batch 8, page 16, max_len 1024, prefill chunk 256;
* the same traffic again, which must compile nothing;
* the same prompts once more with every attention call on the XLA
  path, fed the first pass's tokens (teacher forcing), and the final-
  position logits of both paths compared.

``--chips 4`` runs only the head-parallel decode path on a (data=1,
model=4) mesh — parameters and KV cache sharded by their logical axes
— against the one-chip dense engine on the same weights.

The last line of standard output is ``{"ok": true, "device": ...}``;
it is printed only when every check passed.  Without a TPU the script
exits non-zero before serving.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

LAYERS = 16                       # of starcoder2-7b's 32
BATCH, MAX_LEN, PAGE, CHUNK = 8, 1024, 16, 256
PROMPTS = (256, 512, 768, 256, 512, 768)
MAX_NEW = 64
#: relative L2 gap allowed between two paths' final-position logits.
#: bf16 rounds to 2**-8 ~ 3.9e-3 of a value; the paths round in
#: different places (in-kernel f32 vs materialised bf16 Q, the output
#: projection and residual add in or out of the kernel), so each of the
#: 32 sublayers feeding the bf16 residual stream can differ by about
#: one rounding.  Independent roundings grow as sqrt(32) * 3.9e-3 ~
#: 2.2e-2; the limit allows a bit over twice that.
LOGIT_RTOL = 5e-2


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    return 1


class Recording:
    """Engine mixin: keeps each request's final-position logits
    (``final[uid]``) and, with ``forced`` set (uid -> tokens), replaces
    every sampled token by the forced one for the same request and
    position — so a second kernel path sees exactly the first path's
    inputs (teacher forcing)."""

    forced = None

    def _setup_recording(self, requests) -> None:
        self.uids = {tuple(r.prompt): r.uid for r in requests}
        self.slot_uid, self.index, self.final = {}, {}, {}

    def begin_prefill(self, slot, prompt):
        super().begin_prefill(slot, prompt)
        self.slot_uid[slot] = self.uids[tuple(prompt)]

    def _force(self, slot: int, tok: int) -> int:
        if self.forced is None:
            return tok
        return self.forced[self.slot_uid[slot]][self.index[slot]]

    def _advance_prefills(self):
        out = []
        for slot, tok in super()._advance_prefills():
            self.index[slot] = 0
            if self.forced is not None:
                tok = self._force(slot, tok)
                self.state = dataclasses.replace(
                    self.state,
                    last_token=self.state.last_token.at[slot].set(tok))
            out.append((slot, tok))
        return out

    def decode_once(self):
        toks = super().decode_once()
        if toks is None:
            return None
        toks = toks.copy()
        for i, alive in enumerate(self.live):
            if not alive:
                continue
            self.index[i] += 1
            toks[i] = self._force(i, int(toks[i]))
            self.final[self.slot_uid[i]] = \
                self.last_logits[i].astype("float32")
        if self.forced is not None:
            import jax.numpy as jnp
            self.state = dataclasses.replace(self.state,
                                             last_token=jnp.asarray(toks))
        return toks


def _engine_classes():
    from repro.serve import (ContinuousBatchingEngine,
                             PagedContinuousBatchingEngine)

    class RecordingDense(Recording, ContinuousBatchingEngine):
        pass

    class RecordingPaged(Recording, PagedContinuousBatchingEngine):
        pass

    return RecordingDense, RecordingPaged


def _serve(engine, cfg, seed: int, forced=None):
    from repro.launch.serve import make_requests, serve
    reqs = make_requests(cfg, PROMPTS, MAX_NEW, seed)
    engine._setup_recording(reqs)
    engine.forced = forced
    t0 = time.perf_counter()
    done = serve(engine, reqs, MAX_LEN)
    return done, time.perf_counter() - t0


def _logit_gap(got: dict, ref: dict):
    """Worst relative L2 gap over requests, worst absolute gap, and how
    many requests' final argmax agree."""
    import numpy as np
    rel = max(float(np.linalg.norm(got[u] - ref[u])
                    / np.linalg.norm(ref[u])) for u in ref)
    absd = max(float(np.max(np.abs(got[u] - ref[u]))) for u in ref)
    agree = sum(int(np.argmax(got[u]) == np.argmax(ref[u])) for u in ref)
    return rel, absd, agree


def one_chip(cfg, seed: int) -> int:
    import jax
    from repro.launch.compilation import count_compiles
    from repro.launch.serve import make_engine
    from repro.models import transformer as tf
    _, RecordingPaged = _engine_classes()

    t0 = time.perf_counter()
    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"weights: {n_params:,} parameters, random from seed {seed} "
          f"({time.perf_counter() - t0:.1f}s)")

    eng = make_engine(params, cfg, batch=BATCH, max_len=MAX_LEN,
                      prefill_chunk=CHUNK, paged=True, page_size=PAGE,
                      engine_cls=RecordingPaged)
    done, secs = _serve(eng, cfg, seed)
    tokens = {r.uid: list(r.generated) for r in done}
    n_tok = sum(len(t) for t in tokens.values())
    print(f"served {len(done)}/{len(PROMPTS)} requests, {n_tok} tokens "
          f"on the paged engine (batch {BATCH}, page {PAGE}, max_len "
          f"{MAX_LEN}, chunk {CHUNK}); smoke timing, not a benchmark: "
          f"{secs:.1f}s with compilation")
    pallas_final = dict(eng.final)

    with count_compiles() as compiles:
        again, secs2 = _serve(eng, cfg, seed)
    print(f"repeat pass: {compiles[0]} compiles; smoke timing, not a "
          f"benchmark: {secs2:.1f}s")
    repeat_same = {r.uid: list(r.generated) for r in again} == tokens

    res = collections.Counter((ph, b, path, impl) for ph, _, b, path, impl
                              in eng.plan.resolutions)
    print("plan.resolutions (phase, bucket, path, impl) x count:")
    for k, n in sorted(res.items()):
        print(f"  {k} x {n}")
    ledger = eng.plan.downgrades
    print(f"downgrade ledger: {[str(d) for d in ledger]}")

    xla_cfg = dataclasses.replace(cfg, attn_impl="xla")
    ref_eng = make_engine(params, xla_cfg, batch=BATCH, max_len=MAX_LEN,
                          prefill_chunk=CHUNK, paged=True, page_size=PAGE,
                          plan=False, engine_cls=RecordingPaged)
    _serve(ref_eng, xla_cfg, seed, forced=tokens)
    rel, absd, agree = _logit_gap(pallas_final, ref_eng.final)
    print(f"Pallas vs XLA final-position logits (teacher-forced, "
          f"{len(pallas_final)} requests): relative L2 {rel:.3e} "
          f"(limit {LOGIT_RTOL:.0e}), max |diff| {absd:.3e}, argmax "
          f"agrees {agree}/{len(pallas_final)}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}")

    if len(done) != len(PROMPTS) or any(
            len(t) != MAX_NEW for t in tokens.values()):
        return _fail("not every request was served in full")
    if ("decode", "decode_megakernel", "pallas") not in {
            (ph, path, impl) for ph, _, path, impl in res}:
        return _fail("no decode step resolved to decode_megakernel/pallas")
    if ledger:
        return _fail(f"downgrades recorded: {ledger}")
    if compiles[0]:
        return _fail(f"{compiles[0]} compiles on the repeat pass")
    if not repeat_same:
        return _fail("the repeat pass generated different tokens")
    if not rel <= LOGIT_RTOL:
        return _fail(f"logit gap {rel:.3e} over {LOGIT_RTOL:.0e}")
    return 0


def four_chips(cfg, seed: int) -> int:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import make_engine
    from repro.models import transformer as tf
    from repro.sharding import param_shardings, set_rules_for_mesh
    RecordingDense, _ = _engine_classes()

    params = tf.init_params(jax.random.PRNGKey(seed), cfg)
    one = make_engine(params, cfg, batch=BATCH, max_len=MAX_LEN,
                      prefill_chunk=CHUNK, engine_cls=RecordingDense)
    done1, secs1 = _serve(one, cfg, seed)
    tokens = {r.uid: list(r.generated) for r in done1}
    ref = dict(one.final)
    print(f"one chip: dense engine served {len(done1)} requests; smoke "
          f"timing, not a benchmark: {secs1:.1f}s with compilation")

    mesh = make_host_mesh(data=1, model=4)
    hp_cfg = dataclasses.replace(cfg, head_parallel_decode=True,
                                 attn_impl="xla")
    shardings = param_shardings(tf.abstract_params(cfg)[1], mesh,
                                like=params)
    sharded = jax.device_put(params, shardings)
    del params, one
    gc.collect()
    with set_rules_for_mesh(mesh):
        eng = make_engine(sharded, hp_cfg, batch=BATCH, max_len=MAX_LEN,
                          prefill_chunk=CHUNK, plan=False, mesh=mesh,
                          engine_cls=RecordingDense)
        done4, secs4 = _serve(eng, hp_cfg, seed, forced=tokens)
    gc.collect()
    print(f"four chips: head-parallel decode on mesh "
          f"{dict(mesh.shape)} served {len(done4)} requests; smoke "
          f"timing, not a benchmark: {secs4:.1f}s with compilation")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in mesh.devices.flat]
    spread = max(in_use) / max(min(in_use), 1) - 1
    print(f"bytes_in_use per device: {in_use} (spread {spread:.1%})")
    rel, absd, agree = _logit_gap(eng.final, ref)
    print(f"4-chip head-parallel vs 1-chip dense final-position logits "
          f"(teacher-forced): relative L2 {rel:.3e} (limit "
          f"{LOGIT_RTOL:.0e}), max |diff| {absd:.3e}, argmax agrees "
          f"{agree}/{len(ref)}")

    if len(done4) != len(PROMPTS):
        return _fail("not every request was served on the mesh")
    if not all(in_use):
        return _fail("a device reports no bytes_in_use")
    if spread > 0.25:
        return _fail(f"device bytes spread {spread:.1%} over 25%")
    if not rel <= LOGIT_RTOL:
        return _fail(f"logit gap {rel:.3e} over {LOGIT_RTOL:.0e}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}")
    if d0.platform != "tpu":
        return _fail(f"needs a TPU; JAX found platform {d0.platform!r}")
    if len(devs) < args.chips:
        return _fail(f"--chips {args.chips} but {len(devs)} devices")

    from repro import configs
    from repro.launch.compilation import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    full = configs.get_config("starcoder2-7b")
    cfg = dataclasses.replace(full, n_layers=LAYERS)
    print(f"config: {cfg}")
    print(f"cut: n_layers {full.n_layers} -> {cfg.n_layers} (depth only;"
          f" widths, heads, vocab and dtypes as published)")
    print(f"traffic: {len(PROMPTS)} requests, prompts {PROMPTS}, "
          f"{MAX_NEW} new tokens each")

    rc = (four_chips if args.chips == 4 else one_chip)(cfg, args.seed)
    if rc:
        return rc
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
