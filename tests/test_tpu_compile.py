"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

The TPU compiler ships with jaxlib, so the chip's lowering rules (block
shapes, Mosaic's supported ops, VMEM limits) are checked here at the
widths the serving path runs: Qwen2-1.5B (2 KV heads × 6 query heads,
head_dim 128 → 16 subspaces) and StableLM-1.6B (32 × 1, head_dim 64 → 8
subspaces), block 128, an 8192-token context, 4 rows. Interpret mode
hides every one of these rules, so each kernel test asserts that the
compiled program really contains the kernel (``tpu_custom_call``). The
candidate selection and the tier table are plain XLA: their tests assert
that the chip's program holds no scatter and no sort (and the tier
table's no gather).

The topology is described inside a fixture (never at import): only the
test process that runs this file loads the TPU compiler library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs

ARCHS = ("qwen2-1.5b", "stablelm-1.6b")
BATCH, N_MAX, BLOCK = 4, 8192, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """Compile ``fn`` at the given shapes for one described chip, with the
    kernels forced on and the persistent compile cache off (a cached TPU
    executable cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def run(fn, *shapes, kernel=True):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert ("tpu_custom_call" in text) == kernel
        return text

    yield run
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _widths(arch):
    cfg = configs.get(arch)
    pcfg = cfg.pariskv
    G = cfg.num_kv_heads
    return dict(G=G, Hg=cfg.num_heads // G, B=pcfg.num_subspaces(cfg.head_dim),
                C=pcfg.num_centroids(), m=pcfg.m,
                cand=pcfg.candidate_count(N_MAX),
                sink=pcfg.sink_size, nb=BATCH * N_MAX // BLOCK)


@pytest.mark.parametrize("arch", ARCHS)
def test_collision_paged_kernel_compiles(compile_tpu, arch):
    from repro.kernels.collision import collision_scores_paged_kernel
    w = _widths(arch)
    compile_tpu(
        lambda ids, bt, t, e: collision_scores_paged_kernel(ids, bt, t, e,
                                                            w["sink"]),
        ((w["nb"], w["G"], BLOCK, w["B"]), jnp.uint8),
        ((BATCH, N_MAX // BLOCK), jnp.int32),
        ((BATCH, w["G"], w["Hg"], w["B"], w["C"]), jnp.int32),
        ((BATCH,), jnp.int32))


@pytest.mark.parametrize("arch", ARCHS)
def test_rerank_paged_kernel_compiles(compile_tpu, arch):
    from repro.kernels.rerank import rerank_paged_kernel
    w = _widths(arch)
    rows = (BATCH, w["G"], w["Hg"])
    compile_tpu(
        lambda c, wt, r, q, qn: rerank_paged_kernel(c, wt, r, q, qn,
                                                    m=w["m"]),
        ((w["nb"], w["G"], BLOCK, w["B"]), jnp.uint32),
        ((w["nb"], w["G"], BLOCK, w["B"]), jnp.float32),
        (rows + (w["cand"],), jnp.int32),
        (rows + (w["B"], w["m"]), jnp.float32),
        (rows, jnp.float32))


# kernels no serving path calls: compiled at the Qwen2-1.5B widths
def test_collision_contiguous_kernel_compiles(compile_tpu):
    from repro.kernels.collision import collision_scores_kernel
    w = _widths("qwen2-1.5b")
    compile_tpu(collision_scores_kernel,
                ((BATCH, N_MAX, w["B"]), jnp.uint8),
                ((BATCH, w["B"], w["C"]), jnp.int32))


def test_rerank_contiguous_kernel_compiles(compile_tpu):
    from repro.kernels.rerank import rerank_kernel
    w = _widths("qwen2-1.5b")
    compile_tpu(rerank_kernel,
                ((N_MAX, w["B"]), jnp.uint32), ((N_MAX, w["B"]), jnp.float32),
                ((w["cand"],), jnp.int32), ((w["B"], w["m"]), jnp.float32),
                ((), jnp.float32))


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_gather_kv_kernel_compiles(compile_tpu, paged):
    from repro.kernels.gather_kv import gather_kv_kernel, gather_kv_paged_kernel
    cfg = configs.get("qwen2-1.5b")
    k, d = cfg.pariskv.top_k, cfg.head_dim
    if paged:
        compile_tpu(gather_kv_paged_kernel,
                    ((BATCH * N_MAX // BLOCK, BLOCK, d), jnp.bfloat16),
                    ((BATCH, N_MAX // BLOCK), jnp.int32),
                    ((BATCH, k), jnp.int32))
    else:
        compile_tpu(gather_kv_kernel, ((BATCH, N_MAX, d), jnp.bfloat16),
                    ((BATCH, k), jnp.int32))


def test_bucket_topk_kernel_compiles(compile_tpu):
    from repro.kernels.bucket_topk import bucket_topk
    w = _widths("qwen2-1.5b")
    score_range = max(configs.get("qwen2-1.5b").pariskv.tier_weights) * w["B"]
    compile_tpu(lambda s: bucket_topk(s, w["cand"], score_range=score_range),
                ((BATCH, N_MAX), jnp.int32))


# the benchmark cells' (b, G, Hg, n) Stage-I scores
SELECT_SHAPES = {"qwen2-1.5b": (1, 2, 6, 32768),
                 "stablelm-1.6b": (4, 32, 1, 2048)}


@pytest.mark.parametrize("arch", ARCHS)
def test_select_candidates_compiles_without_scatter_or_sort(compile_tpu, arch):
    """Candidate selection is plain XLA on the chip, and neither its
    histogram nor its compaction may become a scatter (the TPU serialises
    the updates) or a sort."""
    from repro.core.retrieval import select_candidates_bucket
    cfg = configs.get(arch)
    pcfg = cfg.pariskv
    shape = SELECT_SHAPES[arch]
    C = pcfg.candidate_count(shape[-1])
    score_range = max(pcfg.tier_weights) * pcfg.num_subspaces(cfg.head_dim)
    text = compile_tpu(
        lambda s: select_candidates_bucket(s, C, score_range),
        (shape, jnp.int32), kernel=False)
    # opcodes only: the text also names the test's own function
    assert not re.findall(r"\s(scatter|sort)\(", text)


# the benchmark cells' (b, G, Hg, B, 2^m) tier tables; counts at Hg 1
TIER_SHAPES = {"qwen2-1.5b": (1, 2, 6, 16, 256),
               "stablelm-1.6b": (4, 32, 1, 8, 256),
               "deepseek-v2-lite-16b": (1, 1, 16, 128, 256)}


@pytest.mark.parametrize("arch", TIER_SHAPES)
def test_tier_weight_table_compiles_without_gather_sort_or_scatter(
        compile_tpu, arch):
    """The tier table is plain XLA on the chip and ranks the buckets by
    comparison: no sort, and no gather or scatter through a permutation
    (the TPU runs those an element at a time)."""
    from repro.core.retrieval import tier_weight_table
    pcfg = configs.get(arch).pariskv
    shape = TIER_SHAPES[arch]
    text = compile_tpu(
        lambda cs, counts, n: tier_weight_table(cs, counts, n, pcfg),
        (shape, jnp.float32), (shape[:2] + (1,) + shape[3:], jnp.int32),
        (shape[:1] + (1, 1), jnp.int32), kernel=False)
    assert not re.findall(r"\s(gather|sort|scatter)\(", text)


def test_deepseek_cell_chunk_compiles_with_its_scopes(compile_tpu, one_chip):
    """The ``deepseek-v2-lite-longctx-decode`` cell's decode chunk (5
    layers of DeepSeek-V2-Lite, 64 experts, a 65536-token latent pool of
    512 blocks, chunked fill of 512) compiles for one v5e with the
    Stage-I/II kernels in it, fits the chip, and names its MoE and latent
    ops under ``model.dense``."""
    import dataclasses
    from repro.core import phases
    from repro.models import model as M
    from repro.serving import PagedServingEngine
    cfg = dataclasses.replace(configs.get("deepseek-v2-lite-16b"),
                              num_layers=5, tie_embeddings=False)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    eng = PagedServingEngine(cfg, params, n_max=65536, max_batch=1,
                             block_size=128, chunk_size=8,
                             prefill_budget=512)
    state = jax.eval_shape(eng._init_state)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    bt = jax.ShapeDtypeStruct(eng._bt.shape, jnp.int32, sharding=one_chip)
    compiled = eng._chunk.lower(on_chip(params), on_chip(state), bt).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for sub in phases.SUB:
        assert any(f"{phases.DENSE}/{sub}/" in n for n in names), sub
