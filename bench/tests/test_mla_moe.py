"""The MLA + MoE architecture file (``bench/archs/mla_moe.py``): the
program, served through ``PagedServingEngine`` with chunked fill, agrees
with the arch's float32 reference; the layout is the program's; the
counts at the cell's size are pinned; the cell's new readers run."""
import json

import jax
import numpy as np
import pytest

from bench import counts, weights
from bench import reference as R
from bench.archs import mla_moe
from conftest import REPO, TINY_PARISKV, run, tiny_cell


def cell_config() -> dict:
    return json.loads(
        (REPO / "bench/configs/deepseek-v2-lite.json").read_text())


def tiny_mla(dtype: str = "bfloat16") -> dict:
    """The cell's configuration at a tiny size: 1 dense + 2 MoE layers,
    8 experts top 2 + 1 shared, YaRN as published."""
    return {**cell_config(), "name": "tiny-mla", "hidden_size": 256,
            "num_hidden_layers": 3, "num_attention_heads": 4,
            "num_key_value_heads": 4, "intermediate_size": 512,
            "moe_intermediate_size": 64, "n_routed_experts": 8,
            "num_experts_per_tok": 2, "n_shared_experts": 1,
            "kv_lora_rank": 64, "qk_nope_head_dim": 32,
            "qk_rope_head_dim": 32, "v_head_dim": 32, "vocab_size": 512,
            "max_position_embeddings": 256, "pariskv": TINY_PARISKV,
            "torch_dtype": dtype}


def served_with_logits(mc, params, prompt, n_out, monkeypatch):
    """Serve one request through ``PagedServingEngine`` with chunked fill
    (block 16, budget 32, n_max 256) and record, from inside its decode
    chunk, the logits each served token was taken from: the fill's
    last-position logits, then each decoded row's."""
    from repro.models import serve as SV
    from repro.serving import PagedServingEngine, Request
    L = len(prompt)
    seen = []

    def record(fill_logits, logits, done, active):
        if done:
            seen.append(np.asarray(fill_logits[0]))
        if active[0]:
            seen.append(np.asarray(logits[0]))

    inner = SV.decode_fill_step

    def spy(params, cfg, token, state, fill_tokens, fctx, any_fill, **kw):
        logits, fill_logits, new = inner(params, cfg, token, state,
                                         fill_tokens, fctx, any_fill, **kw)
        done = any_fill & (fctx.start + fctx.valid_n == L)
        jax.debug.callback(record, fill_logits, logits, done, kw["active"],
                           ordered=True)
        return logits, fill_logits, new

    monkeypatch.setattr(SV, "decode_fill_step", spy)
    eng = PagedServingEngine(mc, params, n_max=256, max_batch=1,
                             block_size=16, chunk_size=4, prefill_budget=32)
    req = Request(uid=0, prompt=prompt, max_new_tokens=n_out)
    eng.submit(req)
    eng.run()
    jax.effects_barrier()
    eng.verify_invariants()
    return np.asarray(req.output), np.stack(seen[:n_out])


# Logits here are ~1 in size. float32: the program and the reference
# differ only in summation order (the fill's online softmax, the grouped
# expert matmuls), ~1e-5; 2e-4 leaves room. bfloat16: the program
# rounds activations, the latent cache and the residual stream to 8 bits
# of mantissa (~0.4% each), which over 40 teacher-forced steps puts a
# logit up to ~0.12 off: the dense arch's program is as far from its own
# reference at this size (0.12); 0.15 leaves room.
@pytest.mark.parametrize("dtype,atol", [("float32", 2e-4),
                                        ("bfloat16", 0.15)])
def test_paged_engine_agrees_with_the_reference(dtype, atol, monkeypatch):
    conf = tiny_mla(dtype)
    mc = mla_moe.program_config(conf)
    params = weights.make(mla_moe.layout(mc), mc, 11)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 512, 150).astype(np.int32)
    served, got = served_with_logits(mc, params, prompt, 40, monkeypatch)
    assert len(served) == 40
    np.testing.assert_array_equal(got.argmax(-1), served)
    dm, pk = mla_moe.dims(mc), conf["pariskv"]
    seq_rows, dec_rows = R.shapes_for([(prompt, served)])
    layer = mla_moe.make_layer(dm, pk, 256, lo=False)
    want = np.asarray(mla_moe.request_logits(
        weights.make(mla_moe.layout(mc), mc, 11), dm, pk, prompt, served,
        seq_rows, dec_rows, layer))
    # the prompt (150) passes a promotion at 128 + 16 ... and decoding
    # moves the region end once more
    ends = R.decode_region_ends(pk, 150, 40)
    assert ends[0] == 118 and ends[-1] > ends[0]
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_layout_is_the_programs_at_the_cell_size():
    from repro.models import model as M
    mc = mla_moe.program_config(cell_config())
    lay = mla_moe.layout(mc)
    weights.check_layout(lay, mc, jax.eval_shape(
        lambda: M.init_params(mc, jax.random.PRNGKey(0))))
    n = sum(int(np.prod(s)) for s, *_ in lay.values())
    assert n == 2_839_831_040                  # 2.84 B, all 64 experts
    assert lay["stages/1/l0/moe/experts_up"][0] == (4, 64, 2048, 1408)
    assert lay["unembed"][0] == (2048, 102400)


def test_counts_at_the_cell_size():
    mc = mla_moe.program_config(cell_config())
    pk = cell_config()["pariskv"]
    assert mla_moe.retrieval(mc) == (1, 16, 576, 5)
    assert mla_moe.attn_flops_per_key(mc) == 2 * 16 * (576 + 512) * 5
    assert mla_moe.matrix_params(mc) == 623_116_288
    assert mla_moe.expert_work(mc, 10) == (2 * 10 * 4 * 8 * 3 * 2048 * 1408,
                                           2 * 10 * 4 * 8 * 3 * 2048 * 1408)
    # a region end of 56832 (57344 prompt tokens, local 512): 128
    # subspaces, C at its cap of 4096
    assert counts.stage1(mla_moe, mc, pk, 56832) == (
        5 * (56704 * 128 + 16 * 128 * 256 * 4), 5 * 56704 * 16 * 128)
    assert counts.stage2(mla_moe, mc, pk, 56832) == (
        5 * 4096 * 16 * 128 * 8, 5 * 2 * 4096 * 16 * 1024)


def test_yarn_frequencies_and_scale_are_the_published_ones():
    dm = mla_moe.dims(mla_moe.program_config(cell_config()))
    inv, f = mla_moe.rope_frequencies(dm)
    assert f == 1.0
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    ramp = np.clip((i - 10) / (23 - 10), 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    assert mla_moe.softmax_scale(dm) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rel=1e-12)


@pytest.mark.parametrize("key,val", [
    ("q_lora_rank", 1536), ("routed_scaling_factor", 2.5),
    ("topk_method", "group_limited_greedy"), ("n_group", 8),
    ("scoring_func", "sigmoid")])
def test_refuses_what_the_program_cannot_serve(key, val):
    from bench.harness import BenchError
    with pytest.raises(BenchError, match=key):
        mla_moe.program_config({**tiny_mla(), key: val})


def test_serves_a_tiny_mla_cell_and_reads_its_metrics(root):
    root.write("bench/configs/tiny-mla.json", tiny_mla())
    root.bench["configs"].append(
        {"name": "tiny-mla", "source": "test",
         "file": "bench/configs/tiny-mla.json", "reduced": [],
         "why": "test"})
    cell = tiny_cell(1, 0.2)
    cell["correctness"]["limits"] = {"mean_logit_gap": 0.05}
    root.add_cell("tiny", "tiny-mla", "tiny-closed", cell)
    res = run(root, "tiny", seconds=2.0, trace=True)
    assert res["correct"], res["check"]
    assert res["check"]["tokens_compared"]["value"] >= 60
    # no TPU plane in a CPU trace: the device readers read nothing, and
    # the new ones do not raise
    assert "moe_ms_per_token" not in res["metrics"]
    assert "moe_roofline" not in res["metrics"]


@pytest.mark.parametrize("fault", ["altered", "frozen"])
def test_a_broken_timed_path_fails_the_cells_limit(root, monkeypatch, fault):
    """The faults a batch-1 cell can have, planted in the tiny MLA cell's
    timed path, fail ``deepseek-v2-lite-longctx-decode``'s committed
    limits (the clean run passes them)."""
    from test_faults import _plant, committed_limits
    limits = committed_limits("deepseek-v2-lite-longctx-decode")
    root.write("bench/configs/tiny-mla.json", tiny_mla())
    root.bench["configs"].append(
        {"name": "tiny-mla", "source": "test",
         "file": "bench/configs/tiny-mla.json", "reduced": [],
         "why": "test"})
    cell = tiny_cell(1, 0.2)
    cell["correctness"].update(requests=50, limits=limits)
    root.add_cell("tiny", "tiny-mla", "tiny-closed", cell)
    clean = run(root, "tiny", seed=5, seconds=2.5)
    assert clean["correct"], clean["check"]
    _plant(monkeypatch, fault)
    res = run(root, "tiny", seed=5, seconds=2.5)
    assert not res["correct"]
