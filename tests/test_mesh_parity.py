"""Differential mesh parity: the head-parallel decode path on a forced
2-device host mesh must be token-bit-identical to the single-device
ContinuousBatchingEngine, with zero lengths downgrades on both — plus
unit coverage for sharding/rules.py resolution semantics and the
mesh_for_cores device guard."""

import os
import subprocess
import sys

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.sharding import rules as shrules  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def _run_in_subprocess(script: str, devices: int = 2):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout, out.stdout[-2000:]


SCRIPT_ENGINE_PARITY = r"""
import dataclasses
import jax, numpy as np
from repro.models.common import ModelConfig
from repro.models import init_params_and_axes
from repro.serve import ContinuousBatchingEngine
from repro.sharding import rules as shrules
from repro.launch import mesh_lowering as ml
from repro.kernels import ops
import repro.serve.distributed_decode as dd

assert len(jax.devices()) == 2

cfg = ModelConfig(name="mesh-parity", n_layers=2, d_model=32, n_heads=4,
                  d_ff=64, vocab_size=64, n_kv_heads=2,
                  attn_impl="reference", param_dtype="float32",
                  compute_dtype="float32")
params, _ = init_params_and_axes(jax.random.PRNGKey(0), cfg)
prompts = [np.arange(5) % 60, (np.arange(9) * 7) % 60]

def run(hp):
    c = dataclasses.replace(cfg, head_parallel_decode=hp)
    ops.reset_downgrade_warnings()
    eng = ContinuousBatchingEngine(params, c, batch_size=2, max_len=32)
    eng.begin_prefill(0, prompts[0])
    eng.begin_prefill(1, prompts[1])
    toks = []
    for _ in range(6):
        t, _ins = eng.step()
        toks.append(None if t is None else t.tolist())
    # acceptance: the masked-lengths kernels never downgraded
    assert not any("masked-lengths" in kernel for kernel, _reason
                   in ops._warned_downgrade_reasons), \
        "lengths downgrade hit"
    return toks

base = run(False)

calls = {"n": 0}
orig = dd.head_parallel_decode_attention
def counting(*a, **k):
    calls["n"] += 1
    return orig(*a, **k)
dd.head_parallel_decode_attention = counting

mesh = ml.mesh_for_cores(2)
with shrules.set_rules_for_mesh(mesh):
    sharded = run(True)

assert calls["n"] >= 1, "head-parallel decode path never executed"
assert base == sharded, f"token divergence: {base} vs {sharded}"
print("OK", calls["n"])
"""


SCRIPT_HEAD_PARALLEL_REFERENCE = r"""
import jax, jax.numpy as jnp
from repro.sharding import set_rules_for_mesh
from repro.sharding import rules as shrules
from repro.serve.distributed_decode import head_parallel_decode_attention
from repro.launch.mesh_lowering import mesh_for_cores
from repro.kernels import ref

ks = jax.random.split(jax.random.PRNGKey(0), 4)
q = jax.random.normal(ks[0], (3, 4, 1, 16))
k = jax.random.normal(ks[1], (3, 2, 24, 16))
v = jax.random.normal(ks[2], (3, 2, 24, 16))
wo = jax.random.normal(ks[3], (4, 16, 32)) * 0.1
lengths = jnp.array([24, 7, 1])
mesh = mesh_for_cores(2)
with set_rules_for_mesh(mesh):
    out = jax.jit(lambda *a: head_parallel_decode_attention(*a))(
        q, k, v, lengths, wo)
o = ref.attention_reference(q, k, v, causal=False, lengths=lengths)
exp = jnp.einsum("bhse,hed->bsd", o, wo)
err = float(jnp.abs(out - exp).max())
assert err < 5e-6, err

# rules: divisibility fallback needs a real 2-wide model axis — a
# 3-head tensor on the 2-way axis must fall back to replication
spec = shrules.logical_to_mesh_axes(
    ("batch", "heads", "seq", "head_dim"), None, mesh, shape=(4, 3, 1, 16))
assert tuple(spec) == ("data", None, None, None), spec
spec = shrules.logical_to_mesh_axes(
    ("batch", "heads", "seq", "head_dim"), None, mesh, shape=(4, 4, 1, 16))
assert tuple(spec) == ("data", "model", None, None), spec
print("OK", err)
"""


def test_engine_token_parity_two_devices():
    """N decode steps, 2-device head-parallel mesh serve vs the
    single-device engine: token streams bit-identical, zero lengths
    downgrades, and the sharded path provably executed."""
    _run_in_subprocess(SCRIPT_ENGINE_PARITY)


def test_head_parallel_attention_matches_reference():
    """head_parallel_decode_attention == reference attention + output
    projection on a 2-device mesh, mixed-depth lengths included; plus
    the shape-aware divisibility fallback on a real 2-wide axis."""
    _run_in_subprocess(SCRIPT_HEAD_PARALLEL_REFERENCE)


# ---------------------------------------------------------------------------
# sharding/rules.py unit tests (single device, no mesh needed)
# ---------------------------------------------------------------------------

def test_rule_resolution_default_rules():
    """DEFAULT_RULES resolution without a mesh: named axes map to
    their mesh axes, unknown/None logical axes replicate."""
    spec = shrules.logical_to_mesh_axes(
        ("batch", "heads", "seq", "head_dim"), shrules.DEFAULT_RULES,
        mesh=None)
    assert spec == P(("pod", "data"), "model", None, None)
    spec = shrules.logical_to_mesh_axes(
        (None, "nonexistent-axis"), shrules.DEFAULT_RULES, mesh=None)
    assert spec == P(None, None)


def test_duplicate_mesh_axis_falls_back_to_replication():
    """Two tensor dims resolving to the same mesh axis: first dim
    wins, the second replicates (flax logical-partitioning parity)."""
    spec = shrules.logical_to_mesh_axes(
        ("heads", "kv_heads"), shrules.DEFAULT_RULES, mesh=None)
    assert spec == P("model", None)
    # tuple-rule overlap: "tokens" spans (pod, data, model); a later
    # "heads" dim finds model already used
    spec = shrules.logical_to_mesh_axes(
        ("tokens", "heads"), shrules.DEFAULT_RULES, mesh=None)
    assert spec == P(("pod", "data", "model"), None)


def test_constrain_is_noop_without_mesh():
    x = jnp.arange(8.0).reshape(2, 4)
    assert shrules.constrain(x, "batch", "heads") is x


def test_mesh_for_cores_raises_on_too_few_devices():
    from repro.launch.mesh_lowering import mesh_for_cores
    need = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="devices"):
        mesh_for_cores(need)
