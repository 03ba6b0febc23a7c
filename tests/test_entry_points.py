"""The entry points' contracts on the CPU: a steady serving step
compiles nothing, ``chip_smoke.py`` refuses to run without a TPU, the
compile-cache location rule, and the repairs that stop quiet fallbacks
(mesh sizes, plan auto-dispatch, concrete contexts)."""

import importlib.util
import pathlib

import pytest

# JAX-heavy tier: deselect with -m 'not slow' for the fast core-DSE tier
pytestmark = pytest.mark.slow

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.workload import NotExpressible
from repro.kernels import ops
from repro.launch import compilation
from repro.launch.serve import make_engine, make_requests, serve
from repro.models import init_params

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def starcoder():
    cfg = configs.get_config("starcoder2-7b", smoke=True)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_repeat_traffic_compiles_nothing(starcoder, paged):
    """After one pass warms every launch, the same traffic again —
    chunked prefills, inserts, decode steps across the 2N crossover —
    runs without a single backend compile, and generates the same
    tokens."""
    cfg, params = starcoder
    eng = make_engine(params, cfg, batch=4, max_len=128, prefill_chunk=16,
                      paged=paged, page_size=8)

    def run():
        reqs = make_requests(cfg, (16, 32, 48, 16, 32), 40)
        return {r.uid: r.generated for r in serve(eng, reqs, 128)}

    first = run()
    with compilation.count_compiles() as n:
        again = run()
    assert n[0] == 0
    assert again == first
    paths = {path for ph, _, _, path, _ in eng.plan.resolutions
             if ph == "decode"}
    assert {"unfused", "decode_megakernel"} <= paths
    # one compiled launch per legalised dispatch, not per step
    assert len(eng._launches) <= 4


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(capsys):
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero before serving,
    names the platform it found, and prints no result line."""
    assert jax.devices()[0].platform == "cpu"
    rc = _load_chip_smoke().main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "needs a TPU" in err and "'cpu'" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("env,expected", [
    ({"JAX_COMPILATION_CACHE_DIR": "/somewhere/cache"},
     "/somewhere/cache"),
    ({}, str(ROOT / ".jax_cache")),
], ids=["env-set", "env-unset"])
def test_compile_cache_dir(env, expected):
    assert compilation.cache_dir(env) == expected


@pytest.mark.parametrize("env_set", [True, False])
def test_enable_compile_cache_sets_dir_only_without_env(monkeypatch,
                                                        env_set):
    """The helper names a directory only when the environment does not;
    jax.config is recorded, not changed, so the test leaves the cache
    off."""
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    path = compilation.enable_compile_cache()
    set_dirs = [v for k, v in calls if k == "jax_compilation_cache_dir"]
    if env_set:
        assert path == "/from/env" and set_dirs == []
    else:
        assert path == str(ROOT / ".jax_cache")
        assert set_dirs == [path]
    assert ("jax_enable_compilation_cache", True) in calls


def test_make_host_mesh_raises_past_device_count():
    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    assert make_host_mesh(1, n).devices.size == n
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(1, n + 1)


def test_auto_dispatch_falls_back_only_when_not_expressible(monkeypatch):
    """impl='auto' keeps the backend default for shapes the DSE cannot
    express; any other plan failure surfaces instead of silently
    picking a path."""
    from repro.lower import cache as plan_cache
    q = jnp.ones((1, 2, 8, 16), jnp.float32)

    def not_expressible(**kw):
        raise NotExpressible("toy")
    monkeypatch.setattr(plan_cache, "kernel_plan", not_expressible)
    out = ops.attention(q, q, q)
    assert out.shape == q.shape

    def broken(**kw):
        raise RuntimeError("plan cache broken")
    monkeypatch.setattr(plan_cache, "kernel_plan", broken)
    with pytest.raises(RuntimeError, match="plan cache broken"):
        ops.attention(q, q, q)


def test_concrete_ctx_only_tolerates_tracers():
    cfg = configs.get_config("starcoder2-7b", smoke=True)
    from repro.serve import make_serving_plan
    plan = make_serving_plan(cfg, 128)
    assert plan.concrete_ctx(np.array([3, 9], np.int32)) == 9
    traced = jax.jit(lambda c: jnp.int32(plan.concrete_ctx(c)))(
        jnp.array([3, 9], jnp.int32))
    assert int(traced) == plan.max_len
    with pytest.raises(ValueError):
        plan.concrete_ctx("not a length")
