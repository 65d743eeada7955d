"""Dense attention + gated-MLP models, as the program serves them:
RMSNorm, GQA/MHA attention with whole-head rotary and an optional QKV
bias, SiLU-gated MLP, tied or untied LM head (Qwen2, StableLM-2 with the
departures its config file names).

An architecture file is the model-specific half of the benchmark. A
configuration names one by ``"arch": "<arch>"`` (``dense`` where it
names none) and the harness loads ``bench/archs/<arch>.py``. It gives:

* ``program_config(conf)``: the program's ``ModelConfig`` with the config
  file's numbers put in (so the file is what runs); refuses a file the
  arch or the program cannot serve as published.
* ``layout(mc)``: flat ``path -> (shape, kind)`` of the served parameter
  tree, paths ``stages/<i>/l<j>/...`` for stage ``i``'s layer ``j``
  (stacked on a leading axis of the stage's repeats); ``kind`` is
  ``matrix``, ``bias`` or ``norm``, and a third element such as
  ``"float32"`` names a leaf's type where it is not the served one.
  ``bench.weights`` makes and checks it.
* The plain float32 reference forward, built from ``bench.reference``:
  ``dims(mc)``, ``make_layer(dims, pk, n_max, lo)`` (compiled once, shared
  by every request of a run) and ``request_logits(params, dims, pk,
  prompt, served, seq_rows, dec_rows, layer, lo=False)``; ``lo`` is the
  float8 control.
* Work counts for ``bench.counts``: ``matrix_params(mc)`` (matrix
  parameters active per token, LM head included),
  ``attn_flops_per_key(mc)`` (attention FLOPs per attended key, summed
  over layers) and ``retrieval(mc)`` (``(kv_heads, query_heads,
  head_dim, layers)`` of the keys ParisKV retrieves over).
* ``describe(mc)``: the shape, for the run's ``[cell]`` line.

Nothing here is imported by the program.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.harness import BenchError


# -------------------------------------------------------- configuration ----
def program_config(conf: dict):
    from repro import configs
    from repro.core.config import ParisKVConfig
    base = configs.get(conf["program_config"])
    if base.family != "dense":
        raise BenchError(f"{conf['name']}: the dense arch serves dense "
                         f"attention models only")
    if conf.get("partial_rotary_factor", 1.0) != 1.0 \
            or conf.get("qk_layernorm", False):
        raise BenchError(f"{conf['name']}: the program rotates whole heads "
                         f"and has no q/k norm")
    pk = {k: tuple(v) if isinstance(v, list) else v
          for k, v in conf["pariskv"].items()}
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        qkv_bias=conf["use_qkv_bias"],
        norm_eps=conf.get("rms_norm_eps", conf.get("layer_norm_eps")),
        dtype=conf["torch_dtype"], pariskv=ParisKVConfig(**pk))


def describe(mc) -> str:
    return (f"{mc.num_layers}x{mc.d_model}, heads {mc.num_heads}/"
            f"{mc.num_kv_heads}, head_dim {mc.head_dim}, ffn {mc.d_ff}, "
            f"vocab {mc.vocab_size}")


def layout(mc) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """One stage of ``num_layers`` attention + gated-MLP layers."""
    d, f, v = mc.d_model, mc.d_ff, mc.vocab_size
    H, G, hd, n = mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.num_layers
    lay = {
        "embed": ((v, d), "matrix"),
        "final_norm": ((d,), "norm"),
        "stages/0/l0/norm_attn": ((n, d), "norm"),
        "stages/0/l0/norm_mlp": ((n, d), "norm"),
        "stages/0/l0/attn/wq": ((n, d, H * hd), "matrix"),
        "stages/0/l0/attn/wk": ((n, d, G * hd), "matrix"),
        "stages/0/l0/attn/wv": ((n, d, G * hd), "matrix"),
        "stages/0/l0/attn/wo": ((n, H * hd, d), "matrix"),
        "stages/0/l0/mlp/wi_gate": ((n, d, f), "matrix"),
        "stages/0/l0/mlp/wi_up": ((n, d, f), "matrix"),
        "stages/0/l0/mlp/wo_mlp": ((n, f, d), "matrix"),
    }
    if mc.qkv_bias:
        lay["stages/0/l0/attn/bq"] = ((n, H * hd), "bias")
        lay["stages/0/l0/attn/bk"] = ((n, G * hd), "bias")
        lay["stages/0/l0/attn/bv"] = ((n, G * hd), "bias")
    if not mc.tie_embeddings:
        lay["unembed"] = ((d, v), "matrix")
    return lay


# --------------------------------------------------------------- counts ----
def matrix_params(mc) -> int:
    d, f, v = mc.d_model, mc.d_ff, mc.vocab_size
    H, G, hd = mc.num_heads, mc.num_kv_heads, mc.head_dim
    per_layer = d * (H + 2 * G) * hd + H * hd * d + 3 * d * f
    return mc.num_layers * per_layer + d * v


def attn_flops_per_key(mc) -> int:
    """q·k and p·v: 4 · heads · head_dim per key and layer."""
    return 4 * mc.num_heads * mc.head_dim * mc.num_layers


def retrieval(mc) -> tuple:
    return mc.num_kv_heads, mc.num_heads, mc.head_dim, mc.num_layers


# ------------------------------------------------------------ reference ----
def dims(mc) -> dict:
    return dict(heads=mc.num_heads, kv_heads=mc.num_kv_heads,
                head_dim=mc.head_dim, eps=mc.norm_eps,
                theta=mc.rope_theta, layers=mc.num_layers,
                tied=mc.tie_embeddings)


def make_layer(dm: dict, pk: dict, n_max: int, lo: bool):
    """One jitted layer over a whole request, x (S, d) f32 → x, S a multiple
    of 512. Rows at ``dec_pos`` (T,) are decoded positions with region
    ends ``dec_enc``; every other row attends causally (prompt rows; rows
    past the request are padding and are never read)."""
    H, G, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    Hg = H // G
    signs = jnp.asarray(R.rotation_signs(1 << (hd - 1).bit_length(),
                                         pk["srht_seed"]))
    th, lv = R.magnitude_levels(pk["m"], pk["magnitude_bits"])
    m = pk["m"]
    bits = (np.arange(1 << m)[:, None] >> np.arange(m)[None]) & 1
    centroids = jnp.asarray((bits * 2.0 - 1.0) / np.sqrt(m), jnp.float32)
    n_cand = R.candidate_count(pk, n_max)
    scale = 1.0 / math.sqrt(hd)

    @jax.jit
    def layer(x, w, dec_pos, dec_enc):
        S = x.shape[0]
        pos = jnp.arange(S)
        h = R.rms(x, w["norm_attn"], dm["eps"])
        q, k, v = (R.mm(h, w["wq"], lo), R.mm(h, w["wk"], lo),
                   R.mm(h, w["wv"], lo))
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = R.rope(q.reshape(S, H, hd), pos, dm["theta"])
        k = R.rope(k.reshape(S, G, hd), pos, dm["theta"])
        v = v.reshape(S, G, hd)
        if lo:
            q, k, v = R.round8(q, -1), R.round8(k, -1), R.round8(v, -1)
        qg = q.reshape(S, G, Hg, hd)
        att = R.causal_attention(qg, k, v, scale=scale)
        ids, kt = R.key_metadata(k, signs, pk, th, lv)
        onehot = jax.nn.one_hot(ids, 1 << m, dtype=jnp.bfloat16)
        T = dec_pos.shape[0]
        tb = 32

        def dec_block(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * tb, tb, 0)
            return R.decode_attention(qg[sl(dec_pos)], k, v, onehot, kt,
                                      sl(dec_pos), sl(dec_enc), pk=pk,
                                      signs=signs, scale=scale,
                                      n_cand=n_cand, centroids=centroids)

        out_d = jax.lax.map(dec_block, jnp.arange(T // tb))
        att = att.at[dec_pos].set(out_d.reshape((T, G, Hg, hd)))
        x = x + R.mm(att.reshape(S, H * hd), w["wo"], lo)
        h = R.rms(x, w["norm_mlp"], dm["eps"])
        y = jax.nn.silu(R.mm(h, w["wi_gate"], lo)) * R.mm(h, w["wi_up"], lo)
        return x + R.mm(y, w["wo_mlp"], lo)

    return layer


def request_logits(params, dm: dict, pk: dict, prompt: np.ndarray,
                   served: np.ndarray, seq_rows: int, dec_rows: int,
                   layer, lo: bool = False) -> jax.Array:
    """Logits (T, V) at the T positions that produced the served tokens:
    the last prompt position, then every decoded position, with the
    served tokens fed back (teacher forcing). ``layer`` comes from
    ``make_layer`` (with the same ``lo``); ``seq_rows``/``dec_rows`` from
    ``bench.reference.shapes_for``."""
    L, T = len(prompt), len(served)
    toks = np.zeros(seq_rows, np.int32)
    toks[:L + T - 1] = np.concatenate([prompt, served[:-1]])
    dec_pos = (L + np.arange(dec_rows)).astype(np.int32)
    dec_enc = R.decode_region_ends(pk, L, dec_rows)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(toks)])
        st = params["stages"][0]["l0"]
        for li in range(dm["layers"]):
            w = jax.tree.map(lambda a: f32(a[li]), st)
            w = {**w["attn"], **w["mlp"], "norm_attn": w["norm_attn"],
                 "norm_mlp": w["norm_mlp"]}
            x = layer(x, w, jnp.asarray(dec_pos), jnp.asarray(dec_enc))
        xo = R.rms(x[L - 1:L - 1 + T], f32(params["final_norm"]), dm["eps"])
        head = (f32(params["embed"]).T if dm["tied"]
                else f32(params["unembed"]))
        return R.mm(xo, head, lo)
