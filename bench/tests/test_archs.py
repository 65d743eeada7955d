"""The architecture files: the dense arch computes what the benchmark
computed before it moved into ``bench/archs/dense.py``, and the weights
module takes a layout of any number of stages.

The literals below were computed by the benchmark as it stood before the
move (``bench/counts.py``, ``bench/weights.py`` and ``bench/reference.py``
of that tree, on the CPU); the dense arch has to give them exactly (the
logits to 1e-6).
"""
import json
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import counts, weights
from bench import reference as R
from bench.archs import dense
from conftest import REPO, TINY_PARISKV, tiny_config


def config(name: str) -> dict:
    return json.loads((REPO / f"bench/configs/{name}.json").read_text())


# the two cells' configs at their sizes: a region end (and prompt) of
# 24576 for Qwen2 (n_max 32768) and of 1718 for StableLM (n_max 2048)
COUNTS = {
    "qwen2-1.5b": dict(
        enc=24576, decoded=290, matrix_params=1543569408,
        stage1=(27410432, 131432448), stage2=(84123648, 168247296),
        decode_flops=934773424128, prompt_flops=127823561883648),
    "stablelm-1.6b": dict(
        enc=1718, decoded=61, matrix_params=1438646272,
        stage1=(16060416, 9768960), stage2=(6291456, 12582912),
        decode_flops=181726740480, prompt_flops=5233504092160),
}


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_counts_are_the_ones_before_the_move(name):
    conf = config(name)
    pk, want = conf["pariskv"], COUNTS[name]
    mc = dense.program_config(conf)
    enc = want["enc"]
    assert dense.matrix_params(mc) == want["matrix_params"]
    assert counts.stage1(dense, mc, pk, enc) == want["stage1"]
    assert counts.stage2(dense, mc, pk, enc) == want["stage2"]
    assert counts.decode_flops(dense, mc, pk, enc, 0, want["decoded"]) \
        == want["decode_flops"]
    assert counts.prompt_flops(dense, mc, 0, enc) == want["prompt_flops"]


def _times(n):
    return lambda: [float(j) for j in range(n)]


# traced windows: Qwen2's one session with tokens 250..270 in the window
# (across the region's move at token 257); StableLM with one request
# decoding and one filling its first 512 prompt tokens
TRACED = {
    "qwen2-1.5b": (
        [(0, 24576, 300)], (249.5, 270.5), {0: 24576}, {0: 24576},
        {"flops": 67869892608, "stage1": (568967168, 2720169984),
         "stage2": (1741307904, 3482615808), "decode_tokens": 21}),
    "stablelm-1.6b": (
        [(0, 1718, 61), (1, 960, 0)], (9.5, 40.5), {0: 1718, 1: 0},
        {0: 1718, 1: 512},
        {"flops": 1592852545536, "stage1": (400355328, 205320192),
         "stage2": (195035136, 390070272), "decode_tokens": 31}),
}


@pytest.mark.parametrize("name", sorted(TRACED))
def test_traced_work_is_the_one_before_the_move(name):
    recs, (tt0, tt1), f0, f1, want = TRACED[name]
    conf = config(name)
    run = SimpleNamespace(
        arch=dense, mc=dense.program_config(conf), tt0=tt0, tt1=tt1,
        cell=SimpleNamespace(config=conf), filled={"tt0": f0, "tt1": f1},
        recs=[SimpleNamespace(req=object(), uid=u, prompt=[0] * n,
                              token_times=_times(t)) for u, n, t in recs])
    assert counts.traced_work(run) == want


def _stack(n, d, f, v, H, G, hd):
    return {
        "embed": ([v, d], "matrix"), "final_norm": ([d], "norm"),
        "stages/0/l0/norm_attn": ([n, d], "norm"),
        "stages/0/l0/norm_mlp": ([n, d], "norm"),
        "stages/0/l0/attn/wq": ([n, d, H * hd], "matrix"),
        "stages/0/l0/attn/wk": ([n, d, G * hd], "matrix"),
        "stages/0/l0/attn/wv": ([n, d, G * hd], "matrix"),
        "stages/0/l0/attn/wo": ([n, H * hd, d], "matrix"),
        "stages/0/l0/mlp/wi_gate": ([n, d, f], "matrix"),
        "stages/0/l0/mlp/wi_up": ([n, d, f], "matrix"),
        "stages/0/l0/mlp/wo_mlp": ([n, f, d], "matrix"),
        "stages/0/l0/attn/bq": ([n, H * hd], "bias"),
        "stages/0/l0/attn/bk": ([n, G * hd], "bias"),
        "stages/0/l0/attn/bv": ([n, G * hd], "bias"),
    }


LAYOUTS = {
    "qwen2-1.5b": _stack(28, 1536, 8960, 151936, 12, 2, 128),
    "stablelm-1.6b": {**_stack(24, 2048, 5632, 100352, 32, 32, 64),
                      "unembed": ([2048, 100352], "matrix")},
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_layout_is_the_one_before_the_move(name):
    """Same paths (so the same ``crc32(path)`` key of every leaf), shapes
    and kinds."""
    lay = dense.layout(dense.program_config(config(name)))
    assert {p: (list(s), k) for p, (s, k) in lay.items()} == LAYOUTS[name]


# the tiny test config (``conftest.tiny_config``), weights from seed 7;
# one request: 150 prompt tokens and 40 served tokens drawn by
# ``default_rng(2024)``; n_max 256. Per position: the largest logit and
# its token, of the reference and of the float8 control.
LOGIT_MAX = [
    1.01729154586792, 0.8284581899642944, 0.8804868459701538,
    0.8899654150009155, 1.0156447887420654, 1.2585408687591553,
    0.7763713598251343, 0.7701501846313477, 0.9503331184387207,
    0.8643765449523926, 1.0271037817001343, 0.8998583555221558,
    1.0149872303009033, 1.1081253290176392, 0.8677815198898315,
    0.8149290084838867, 0.9941258430480957, 0.9023921489715576,
    1.025890827178955, 0.7807678580284119, 0.9638876914978027,
    0.8441340923309326, 0.7898688316345215, 0.9374259114265442,
    0.9438372850418091, 0.9874155521392822, 1.006798505783081,
    0.9385733604431152, 0.8569636344909668, 1.160325527191162,
    0.9390953183174133, 1.0066421031951904, 0.8811107873916626,
    0.8607107400894165, 1.1075741052627563, 1.0214743614196777,
    0.9691240787506104, 0.809173583984375, 0.8039581775665283,
    0.9131830930709839]
ARGMAX = [
    147, 280, 422, 55, 212, 427, 55, 280, 213, 462, 280, 427, 280, 280, 281,
    323, 280, 325, 462, 60, 280, 55, 178, 55, 325, 280, 25, 463, 462, 280,
    462, 462, 366, 462, 123, 46, 253, 253, 70, 462]
LOGIT_MAX_LO = [
    1.0080887079238892, 0.8373305797576904, 0.8774932622909546,
    0.9388452768325806, 0.9730355739593506, 1.267585277557373,
    0.7714338302612305, 0.8168953657150269, 0.8880109786987305,
    0.8731383085250854, 1.0243452787399292, 0.942229151725769,
    0.998725414276123, 1.109740972518921, 0.8430657386779785,
    0.8540035486221313, 0.9650474786758423, 0.9560205936431885,
    1.0326117277145386, 0.7850503325462341, 0.9119051694869995,
    0.8610760569572449, 0.7979602217674255, 0.9309535026550293,
    0.9828793406486511, 1.0024551153182983, 0.9249367713928223,
    0.9164498448371887, 0.9253877997398376, 1.0946478843688965,
    0.9728833436965942, 1.018170714378357, 0.8988067507743835,
    0.8872638940811157, 1.131812334060669, 1.012585163116455,
    1.0612843036651611, 0.8345820903778076, 0.9430058002471924,
    0.8608047366142273]
ARGMAX_LO = [
    147, 323, 422, 55, 212, 427, 330, 280, 213, 462, 280, 427, 280, 280, 281,
    323, 280, 325, 462, 55, 280, 494, 462, 280, 53, 280, 25, 185, 462, 280,
    462, 462, 366, 462, 123, 46, 253, 477, 462, 70]


@pytest.mark.parametrize("lo", [False, True])
def test_reference_logits_are_the_ones_before_the_move(lo):
    mc = dense.program_config(tiny_config("tiny"))
    pk = TINY_PARISKV
    rng = np.random.default_rng(2024)
    prompt = rng.integers(0, 512, 150).astype(np.int32)
    served = rng.integers(0, 512, 40).astype(np.int32)
    params = weights.make(dense.layout(mc), mc, 7)
    dm = dense.dims(mc)
    seq_rows, dec_rows = R.shapes_for([(prompt, served)])
    layer = dense.make_layer(dm, pk, 256, lo=lo)
    logits = np.asarray(dense.request_logits(
        params, dm, pk, prompt, served, seq_rows, dec_rows, layer, lo=lo))
    assert logits.shape == (40, 512)
    np.testing.assert_allclose(logits.max(-1),
                               LOGIT_MAX_LO if lo else LOGIT_MAX,
                               rtol=0, atol=1e-6)
    assert logits.argmax(-1).tolist() == (ARGMAX_LO if lo else ARGMAX)


@pytest.mark.parametrize("conf,match", [
    ({"program_config": "deepseek-v2-lite-16b"}, "dense attention"),
    ({"partial_rotary_factor": 0.25}, "whole heads"),
    ({"qk_layernorm": True}, "q/k norm"),
])
def test_dense_refuses_what_it_cannot_serve(conf, match):
    from bench.harness import BenchError
    with pytest.raises(BenchError, match=match):
        dense.program_config({**tiny_config("tiny"), **conf})


# the program's deepseek-smoke tree: a dense-FFN stage of one MLA layer
# and a MoE stage of two, its router kept in float32
def _mla(n, d=256, r=64, rope=32, H=4, nope=32, v=32):
    a = "attn/"
    return {a + "kv_norm": ([n, r], "norm"),
            a + "w_dkv": ([n, d, r + rope], "matrix"),
            a + "w_uk": ([n, r, H * nope], "matrix"),
            a + "w_uv": ([n, r, H * v], "matrix"),
            a + "wo": ([n, H * v, d], "matrix"),
            a + "wq": ([n, d, H * (nope + rope)], "matrix"),
            "norm_attn": ([n, d], "norm"), "norm_mlp": ([n, d], "norm")}


def _mlp(n, d, f, pre="mlp/"):
    return {pre + "wi_gate": ([n, d, f], "matrix"),
            pre + "wi_up": ([n, d, f], "matrix"),
            pre + "wo_mlp": ([n, f, d], "matrix")}


def two_stage_layout() -> dict:
    s0 = {**_mla(1), **_mlp(1, 256, 512)}
    s1 = {**_mla(2), **_mlp(2, 256, 128, "moe/shared/"),
          "moe/experts_down": ([2, 4, 128, 256], "matrix"),
          "moe/experts_gate": ([2, 4, 256, 128], "matrix"),
          "moe/experts_up": ([2, 4, 256, 128], "matrix"),
          "moe/router": ([2, 256, 4], "matrix", "float32")}
    lay = {"embed": ([512, 256], "matrix"), "final_norm": ([256], "norm")}
    for i, stage in ((1, s1), (0, s0)):        # out of order on purpose
        lay.update({f"stages/{i}/l0/{p}": (tuple(s), *rest)
                    for p, (s, *rest) in stage.items()})
    return {p: (tuple(s), *rest) for p, (s, *rest) in lay.items()}


def test_a_two_stage_layout_is_made_and_checked():
    from repro import configs
    from repro.models import model as M
    mc = configs.smoke("deepseek-v2-lite-16b")
    theirs = jax.eval_shape(lambda: M.init_params(mc, jax.random.PRNGKey(0)))
    lay = two_stage_layout()
    weights.check_layout(lay, mc, theirs)
    ours = jax.eval_shape(lambda: weights.make(lay, mc, 3))
    assert len(ours["stages"]) == 2
    assert ours["stages"][0]["l0"]["mlp"]["wi_gate"].shape == (1, 256, 512)
    assert ours["stages"][1]["l0"]["moe"]["router"].dtype == np.float32
    bad = dict(lay)
    bad["stages/1/l0/moe/experts_up"] = ((2, 4, 256, 64), "matrix")
    with pytest.raises(SystemExit, match="experts_up"):
        weights.check_layout(bad, mc, theirs)
