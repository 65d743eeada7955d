"""Unit tests for the launch layer: sharding name-rules, HLO collective
parser, roofline math — all single-device safe (no 512-device flags)."""
import jax
import numpy as np
import pytest

from repro.launch import mesh as MX


def _fake_mesh():
    """1-device mesh with production axis names (divisibility rules then
    trivially pass — we only check axis *placement* logic)."""
    return jax.make_mesh((1, 1), ("data", "model"))


def test_param_spec_rules():
    mesh = _fake_mesh()
    P = jax.sharding.PartitionSpec
    cases = {
        ("embed", (1024, 64), False): P("model", ("data",)),
        ("stages/0/l0/attn/wq", (8, 64, 128), True): P(None, ("data",), "model"),
        ("stages/0/l0/attn/wo", (8, 128, 64), True): P(None, "model", ("data",)),
        ("stages/0/l0/mlp/wi_gate", (8, 64, 256), True): P(None, ("data",), "model"),
        ("stages/0/l0/moe/experts_gate", (8, 4, 64, 128), True):
            P(None, None, ("data",), "model"),
        ("stages/0/l0/moe/experts_down", (8, 4, 128, 64), True):
            P(None, None, "model", ("data",)),
        ("stages/0/l0/norm_attn", (8, 64), True): P(None, None),
        ("final_norm", (64,), False): P(None),
    }
    for (path, shape, stacked), want in cases.items():
        got = MX.param_spec(path, shape, mesh, multi_pod=False,
                            stacked=stacked)
        assert tuple(got) == tuple(want), (path, got, want)


def test_param_spec_drops_indivisible_axes():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # craft a mesh-shape lookup where model=16 would not divide dim 10 —
    # with the 1-device mesh everything divides; test the guard directly:
    assert MX._divisible(10, mesh, "model")  # 1 device divides
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    assert not MX._divisible(10, FakeMesh, "model")
    assert MX._divisible(32, FakeMesh, "model")
    assert not MX._divisible(8, FakeMesh, ("data", "model"))


def test_batch_axes_small_batch_returns_none():
    mesh = _fake_mesh()
    assert MX.batch_axes(mesh, 4) == ("data",)
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}
    assert MX.batch_axes(FakeMesh, 1) is None      # long_500k case
    assert MX.batch_axes(FakeMesh, 128) == ("data",)


def test_collective_bytes_parser():
    hlo = """
  %all-gather.1 = bf16[4,2048]{1,0} all-gather(%p0), replica_groups={}
  %x = f32[8] add(%a, %b)
  ROOT %all-reduce.2 = (f32[128]{0}, f32[64]{0}) all-reduce(%c, %d)
  %all-to-all.3 = u8[1024]{0} all-to-all(%e)
  %collective-permute.9 = f32[16,16]{1,0} collective-permute(%f)
"""
    out = MX.collective_bytes(hlo)
    assert out["all-gather"] == 4 * 2048 * 2
    assert out["all-reduce"] == (128 + 64) * 4
    assert out["all-to-all"] == 1024
    assert out["collective-permute"] == 16 * 16 * 4
    assert out["total"] == sum(out[k] for k in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute",
        "reduce-scatter"))


def test_roofline_terms_math():
    from benchmarks.bench_roofline import terms
    rec = dict(arch="qwen2-1.5b", shape="decode_32k", chips=256,
               flops=197e12 * 0.001, bytes_accessed=819e9 * 0.002,
               collectives_compiled={"total": 50e9 * 0.003})
    t = terms(rec)
    np.testing.assert_allclose(t["t_compute"], 0.001, rtol=1e-6)
    np.testing.assert_allclose(t["t_memory"], 0.002, rtol=1e-6)
    np.testing.assert_allclose(t["t_collective"], 0.003, rtol=1e-6)
    assert t["dominant"] == "collective"


def test_roofline_trip_count_correction():
    from benchmarks.bench_roofline import corrected
    rec = dict(arch="a", shape="s", flops=10.0, bytes_accessed=20.0,
               collectives_compiled={"total": 5})
    bodies = {("a", "s"): [dict(flops=1.0, bytes=2.0, coll=1, repeat=11)]}
    f, b, c, was = corrected(rec, bodies)
    assert was and f == 10 + 10 * 1.0 and b == 20 + 10 * 2.0 and c == 5 + 10


def test_model_flops_per_device():
    from benchmarks.bench_roofline import model_flops_per_device
    f_train = model_flops_per_device("qwen2-1.5b", "train_4k", 256)
    f_dec = model_flops_per_device("qwen2-1.5b", "decode_32k", 256)
    assert f_train > f_dec * 1000     # train crunches ~1M tokens vs 128
    assert f_dec > 0


@pytest.mark.parametrize("kind,kw,want", [
    ("slots", {}, "ServingEngine"),
    ("wave", {}, "WaveServingEngine"),
    ("paged", {}, "PagedServingEngine"),
    ("paged", {"offload": True}, "OffloadedPagedServingEngine"),
])
def test_build_engine_constructs_each_engine(kind, kw, want):
    """The launcher's one constructor (chip_smoke.py builds through it)."""
    from repro import configs
    from repro.launch.serve import build_engine
    cfg = configs.smoke("qwen2-1.5b")
    eng = build_engine(cfg, None, engine=kind, n_max=256, max_batch=2,
                       block_size=64, prefill_budget=32 if kind != "wave"
                       else 0, **kw)
    try:
        assert type(eng).__name__ == want
    finally:
        if hasattr(eng, "close"):
            eng.close()


def test_build_engine_rejects_paged_only_settings():
    from repro import configs
    from repro.launch.serve import build_engine
    cfg = configs.smoke("qwen2-1.5b")
    with pytest.raises(ValueError):
        build_engine(cfg, None, engine="slots", offload=True)
    with pytest.raises(ValueError):
        build_engine(cfg, None, engine="paged", use_pariskv=False)


def test_compile_cache_dir_env_then_repo(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache is <repo>/.jax_cache."""
    from pathlib import Path
    from repro.launch.serve import enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = enable_compile_cache()
        assert Path(got) == Path(__file__).resolve().parents[1] / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_importing_the_package_initialises_no_backend():
    """Importing any module of ``repro`` must not start a JAX backend: on
    a TPU host that would take the chip in every process that merely
    imports the code. Checked in a fresh interpreter, since this test
    process has long since started its backend."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = Path(__file__).resolve().parents[1] / "src"
    code = (
        "import importlib, pkgutil, repro\n"
        "from jax._src import xla_bridge as xb\n"
        "for m in pkgutil.walk_packages(repro.__path__, 'repro.'):\n"
        "    importlib.import_module(m.name)\n"
        "    assert not xb._backends, m.name\n")
    env = dict(os.environ, PYTHONPATH=str(src),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stderr[-2000:]
