"""Multi-head latent attention (MLA) + mixture-of-experts models, as the
program serves them (DeepSeek-V2-Lite): RMSNorm; MLA with no q-lora,
whose keys and values come from one latent ``[c_kv; k_rope]`` per token
(``kv_lora_rank`` + ``qk_rope_head_dim`` wide, ``c_kv`` RMS-normed) and
YaRN rope on the rope dims; the first ``first_k_dense_replace`` layers a
SiLU-gated MLP, the rest ``n_routed_experts`` SiLU-gated experts of which
each token takes the top ``num_experts_per_tok`` by softmax probability
(the gates those probabilities, renormalised only with
``norm_topk_prob``) plus ``n_shared_experts`` shared ones; untied head.

The interface is ``bench/archs/dense.py``'s (see its docstring), plus
``expert_work`` for the ``moe_roofline`` metric.

The reference, in float32 at ``highest`` precision, imports nothing of
the program:

* prompt rows: MLA in its decompressed form (per-head keys
  ``[c_kv W_UK; k_rope]`` and values ``c_kv W_UV``), causal;
* decoded rows: ParisKV over the latent, as the program retrieves it:
  ``bench.reference.decode_attention`` at G = 1, Hg = heads, head dim
  ``kv_lora_rank + qk_rope_head_dim``, with the absorbed query
  ``[q_nope W_UK^T; q_rope]`` and the latent as key and value, whose
  ``c_kv`` part then decompresses through ``W_UV``;
* MoE without drops, computed on routed tokens only: the largest expert
  load is read to the host, each expert's tokens are gathered into a
  buffer of that size rounded up to 512, and the experts run as batched
  einsums, eight experts at a time.

Each phase of a layer is a jitted function of its own, so what one
phase holds is freed before the next (a 57k-token request's Stage-I
one-hot alone is 3.9 GB).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.harness import BenchError

EXPERT_GROUP = 8      # experts per batched einsum of the reference
ROWS = 512            # expert buffers are rounded up to this many rows


# -------------------------------------------------------- configuration ----
def program_config(conf: dict):
    from repro import configs
    from repro.core.config import ParisKVConfig, YarnRope
    base = configs.get(conf["program_config"])
    if not base.kv_lora_rank or base.family != "moe":
        raise BenchError(f"{conf['name']}: the mla_moe arch serves MLA + "
                         f"MoE models only")
    refused = {
        "q_lora_rank": conf.get("q_lora_rank") is not None,
        "routed_scaling_factor": conf.get("routed_scaling_factor", 1) != 1,
        "topk_method": conf.get("topk_method", "greedy") != "greedy",
        "n_group": conf.get("n_group", 1) != 1
        or conf.get("topk_group", 1) != 1,
        "scoring_func": conf.get("scoring_func", "softmax") != "softmax",
        "moe_layer_freq": conf.get("moe_layer_freq", 1) != 1,
        "attention_bias": conf.get("attention_bias", False),
        "hidden_act": conf.get("hidden_act", "silu") != "silu",
        "rope_scaling": (conf.get("rope_scaling") or {}).get("type",
                                                              "yarn")
        != "yarn",
    }
    bad = sorted(k for k, v in refused.items() if v)
    if bad:
        raise BenchError(f"{conf['name']}: the program cannot serve "
                         f"{bad} as published (q-lora, scaled or "
                         f"group-limited routing, a sigmoid router, "
                         f"sparse MoE layer spacing, biases or a rope "
                         f"scaling other than YaRN)")
    rs = conf.get("rope_scaling")
    yarn = None if rs is None else YarnRope(
        factor=float(rs["factor"]),
        original_max_position_embeddings=rs[
            "original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]))
    pk = {k: tuple(v) if isinstance(v, list) else v
          for k, v in conf["pariskv"].items()}
    return dataclasses.replace(
        base, num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"],
        d_ff=conf["moe_intermediate_size"], vocab_size=conf["vocab_size"],
        num_experts=conf["n_routed_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        num_shared_experts=conf["n_shared_experts"],
        moe_d_ff=conf["moe_intermediate_size"],
        first_dense_layers=conf["first_k_dense_replace"],
        first_dense_d_ff=conf["intermediate_size"],
        norm_topk_prob=conf["norm_topk_prob"],
        kv_lora_rank=conf["kv_lora_rank"], q_lora_rank=0,
        rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"], rope_theta=float(conf["rope_theta"]),
        rope_scaling=yarn, tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], dtype=conf["torch_dtype"],
        pariskv=ParisKVConfig(**pk))


def describe(mc) -> str:
    return (f"{mc.num_layers}x{mc.d_model} ({mc.first_dense_layers} dense), "
            f"MLA heads {mc.num_heads}, latent {mc.kv_lora_rank}+"
            f"{mc.rope_head_dim}, experts {mc.num_experts} top "
            f"{mc.experts_per_token} + {mc.num_shared_experts} shared of "
            f"{mc.moe_d_ff}, dense ffn {mc.first_dense_d_ff}, vocab "
            f"{mc.vocab_size}")


def _attn_layout(pre: str, n: int, mc) -> dict:
    d, H = mc.d_model, mc.num_heads
    r, dr, dn, dv = (mc.kv_lora_rank, mc.rope_head_dim, mc.head_dim,
                     mc.v_head_dim)
    return {
        f"{pre}/norm_attn": ((n, d), "norm"),
        f"{pre}/norm_mlp": ((n, d), "norm"),
        f"{pre}/attn/wq": ((n, d, H * (dn + dr)), "matrix"),
        f"{pre}/attn/w_dkv": ((n, d, r + dr), "matrix"),
        f"{pre}/attn/kv_norm": ((n, r), "norm"),
        f"{pre}/attn/w_uk": ((n, r, H * dn), "matrix"),
        f"{pre}/attn/w_uv": ((n, r, H * dv), "matrix"),
        f"{pre}/attn/wo": ((n, H * dv, d), "matrix"),
    }


def layout(mc) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Stage 0: the leading dense layers; stage 1: the MoE layers."""
    d, v = mc.d_model, mc.vocab_size
    nd, nm = mc.first_dense_layers, mc.num_layers - mc.first_dense_layers
    f, fe, E = mc.first_dense_d_ff, mc.moe_d_ff, mc.num_experts
    fs = mc.num_shared_experts * fe
    lay = {"embed": ((v, d), "matrix"), "final_norm": ((d,), "norm")}
    stage = 0
    if nd:
        pre = "stages/0/l0"
        lay.update(_attn_layout(pre, nd, mc))
        lay.update({f"{pre}/mlp/wi_gate": ((nd, d, f), "matrix"),
                    f"{pre}/mlp/wi_up": ((nd, d, f), "matrix"),
                    f"{pre}/mlp/wo_mlp": ((nd, f, d), "matrix")})
        stage = 1
    pre = f"stages/{stage}/l0"
    lay.update(_attn_layout(pre, nm, mc))
    lay.update({
        f"{pre}/moe/router": ((nm, d, E), "matrix", "float32"),
        f"{pre}/moe/experts_gate": ((nm, E, d, fe), "matrix"),
        f"{pre}/moe/experts_up": ((nm, E, d, fe), "matrix"),
        f"{pre}/moe/experts_down": ((nm, E, fe, d), "matrix")})
    if mc.num_shared_experts:
        lay.update({f"{pre}/moe/shared/wi_gate": ((nm, d, fs), "matrix"),
                    f"{pre}/moe/shared/wi_up": ((nm, d, fs), "matrix"),
                    f"{pre}/moe/shared/wo_mlp": ((nm, fs, d), "matrix")})
    if not mc.tie_embeddings:
        lay["unembed"] = ((d, v), "matrix")
    return lay


# --------------------------------------------------------------- counts ----
def _attn_params(mc) -> int:
    d, H = mc.d_model, mc.num_heads
    r, dr, dn, dv = (mc.kv_lora_rank, mc.rope_head_dim, mc.head_dim,
                     mc.v_head_dim)
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def _expert_params(mc) -> int:
    """Matrix parameters of the experts one token runs in one MoE layer:
    its top-k routed experts and the shared ones."""
    return ((mc.experts_per_token + mc.num_shared_experts) * 3 * mc.d_model
            * mc.moe_d_ff)


def matrix_params(mc) -> int:
    """Active per token: attention, the dense layers' MLP, the routed
    top-k and shared experts and the router of each MoE layer, the head."""
    d = mc.d_model
    nd, nm = mc.first_dense_layers, mc.num_layers - mc.first_dense_layers
    return (mc.num_layers * _attn_params(mc)
            + nd * 3 * d * mc.first_dense_d_ff
            + nm * (_expert_params(mc) + d * mc.num_experts)
            + d * mc.vocab_size)


def attn_flops_per_key(mc) -> int:
    """The absorbed form MLA's decode is defined by: per key and layer,
    each head scores the (r + dr)-wide latent and mixes its r-wide
    ``c_kv``: 2 · heads · (2r + dr)."""
    r, dr = mc.kv_lora_rank, mc.rope_head_dim
    return 2 * mc.num_heads * (r + dr + r) * mc.num_layers


def retrieval(mc) -> tuple:
    """One latent key shared by every head."""
    return 1, mc.num_heads, mc.kv_lora_rank + mc.rope_head_dim, mc.num_layers


def expert_work(mc, tokens: int) -> tuple:
    """(weight bytes, FLOPs) of the routed and shared experts for
    ``tokens`` tokens decoded one at a time: each reads its experts'
    weights (bfloat16) once and multiplies by them, in every MoE layer."""
    nm = mc.num_layers - mc.first_dense_layers
    params = tokens * nm * _expert_params(mc)
    return 2 * params, 2 * params


# ------------------------------------------------------------ reference ----
def dims(mc) -> dict:
    ys = mc.rope_scaling
    return dict(heads=mc.num_heads, nope=mc.head_dim, rope=mc.rope_head_dim,
                v=mc.v_head_dim, lora=mc.kv_lora_rank, eps=mc.norm_eps,
                theta=mc.rope_theta, layers=mc.num_layers,
                dense_layers=mc.first_dense_layers, experts=mc.num_experts,
                top_k=mc.experts_per_token, norm_topk=mc.norm_topk_prob,
                shared=mc.num_shared_experts, tied=mc.tie_embeddings,
                yarn=None if ys is None else dict(
                    factor=ys.factor, L0=ys.original_max_position_embeddings,
                    beta_fast=ys.beta_fast, beta_slow=ys.beta_slow,
                    mscale=ys.mscale, mscale_all_dim=ys.mscale_all_dim))


def _mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def rope_frequencies(dm: dict) -> Tuple[np.ndarray, float]:
    """(inverse frequency per rotated pair, cos/sin factor): YaRN as the
    published modeling code builds it, else plain rope."""
    d, theta, y = dm["rope"], dm["theta"], dm["yarn"]
    extra = theta ** (-np.arange(d // 2, dtype=np.float64) * 2 / d)
    if y is None:
        return extra.astype(np.float32), 1.0

    def corr(beta):
        return d * math.log(y["L0"] / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    lo = max(math.floor(corr(y["beta_fast"])), 0)
    hi = min(math.ceil(corr(y["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    inv = extra / y["factor"] * ramp + extra * (1 - ramp)
    return inv.astype(np.float32), (_mscale(y["factor"], y["mscale"])
                                    / _mscale(y["factor"],
                                              y["mscale_all_dim"]))


def softmax_scale(dm: dict) -> float:
    s = (dm["nope"] + dm["rope"]) ** -0.5
    y = dm["yarn"]
    if y is not None and y["mscale_all_dim"]:
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def _rope(x, pos, inv, f):
    """Half-split rotation (as the program rotates) of x (S, ..., d)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = (jnp.cos(ang).reshape(shape) * f,
                jnp.sin(ang).reshape(shape) * f)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _emm(spec, a, w, lo, w_axis):
    """einsum ``spec`` of activations ``a`` (contracted on their last
    axis) and weights ``w`` (contracted on ``w_axis``); with ``lo`` both
    in float8 like ``bench.reference.mm``."""
    if lo:
        a, w = R.round8(a, -1), R.round8(w, w_axis)
    return jnp.einsum(spec, a, w)


def make_layer(dm: dict, pk: dict, n_max: int, lo: bool) -> dict:
    """The jitted phases of one layer over a whole request of S rows (S a
    multiple of 512; rows at ``dec_pos`` are decoded positions with region
    ends ``dec_enc``, the others prompt rows or never-read padding):
    ``attn`` (x, w, dec_pos, dec_enc) → x after attention; ``mlp`` (x, w)
    → x after the dense FFN; ``moe`` (x, w) → x after the MoE FFN."""
    H, dn, dr, dv, r = (dm["heads"], dm["nope"], dm["rope"], dm["v"],
                        dm["lora"])
    D = r + dr
    signs = jnp.asarray(R.rotation_signs(1 << (D - 1).bit_length(),
                                         pk["srht_seed"]))
    th, lv = R.magnitude_levels(pk["m"], pk["magnitude_bits"])
    m = pk["m"]
    bits = (np.arange(1 << m)[:, None] >> np.arange(m)[None]) & 1
    centroids = jnp.asarray((bits * 2.0 - 1.0) / np.sqrt(m), jnp.float32)
    n_cand = R.candidate_count(pk, n_max)
    scale = softmax_scale(dm)
    inv, f = rope_frequencies(dm)
    eps = dm["eps"]

    @jax.jit
    def prompt(x, w):
        """Decompressed causal attention of every row → (att (S, H, dv),
        latent (S, r+dr), q_nope (S, H, dn), rope'd q_rope (S, H, dr))."""
        S = x.shape[0]
        pos = jnp.arange(S)
        h = R.rms(x, w["norm_attn"], eps)
        q = R.mm(h, w["wq"], lo).reshape(S, H, dn + dr)
        q_n, q_r = q[..., :dn], _rope(q[..., dn:], pos, inv, f)
        ckv = R.mm(h, w["w_dkv"], lo)
        c = R.rms(ckv[:, :r], w["kv_norm"], eps)
        k_r = _rope(ckv[:, r:], pos, inv, f)
        k_n = R.mm(c, w["w_uk"], lo).reshape(S, H, dn)
        v = R.mm(c, w["w_uv"], lo).reshape(S, H, dv)
        qf = jnp.concatenate([q_n, q_r], -1)
        k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, None],
                                                   (S, H, dr))], -1)
        if lo:
            qf, k, v = R.round8(qf, -1), R.round8(k, -1), R.round8(v, -1)
        # causal_attention keeps the query's width: values padded to it
        v = jnp.pad(v, ((0, 0), (0, 0), (0, dn + dr - dv)))
        att = R.causal_attention(qf[:, :, None], k, v, scale=scale)
        return (att[:, :, 0, :dv], jnp.concatenate([c, k_r], -1), q_n,
                q_r)

    @jax.jit
    def decode(att, lat, q_n, q_r, w, dec_pos, dec_enc):
        """ParisKV attention of the decoded rows over the latent, then
        the decompression; written into ``att`` (S, H, dv)."""
        w_uk = w["w_uk"].reshape(r, H, dn)
        w_uv = w["w_uv"].reshape(r, H, dv)
        q = jnp.concatenate(
            [_emm("thd,rhd->thr", q_n[dec_pos], w_uk, lo, -1),
             q_r[dec_pos]], -1)                                # (T, H, D)
        k = lat[:, None]                                       # (S, 1, D)
        if lo:
            q, k = R.round8(q, -1), R.round8(k, -1)
        ids, kt = R.key_metadata(k, signs, pk, th, lv)
        onehot = jax.nn.one_hot(ids, 1 << m, dtype=jnp.bfloat16)
        qg = q[:, None]                                        # (T, 1, H, D)
        tb = 32

        def dec_block(i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * tb, tb, 0)
            o = R.decode_attention(sl(qg), k, k, onehot, kt, sl(dec_pos),
                                   sl(dec_enc), pk=pk, signs=signs,
                                   scale=scale, n_cand=n_cand,
                                   centroids=centroids)
            return _emm("thr,rhv->thv", o[:, 0, :, :r], w_uv, lo, 0)

        T = dec_pos.shape[0]
        out = jax.lax.map(dec_block, jnp.arange(T // tb))
        return att.at[dec_pos].set(out.reshape(T, H, dv))

    @jax.jit
    def out_proj(x, att, w):
        S = x.shape[0]
        return x + R.mm(att.reshape(S, H * dv), w["wo"], lo)

    def attn(x, w, dec_pos, dec_enc):
        att, lat, q_n, q_r = prompt(x, w)
        att = decode(att, lat, q_n, q_r, w, dec_pos, dec_enc)
        del lat, q_n, q_r
        return out_proj(x, att, w)

    def gated(h, g, u, d_):
        return R.mm(jax.nn.silu(R.mm(h, g, lo)) * R.mm(h, u, lo), d_, lo)

    @jax.jit
    def mlp(x, w):
        h = R.rms(x, w["norm_mlp"], eps)
        return x + gated(h, w["wi_gate"], w["wi_up"], w["wo_mlp"])

    @jax.jit
    def route(x, w):
        """→ (normed x, experts (S, k), gates (S, k), load per expert)."""
        h = R.rms(x, w["norm_mlp"], eps)
        probs = jax.nn.softmax(R.mm(h, w["router"], lo), -1)
        g, e = jax.lax.top_k(probs, dm["top_k"])
        if dm["norm_topk"]:
            g = g / jnp.sum(g, -1, keepdims=True)
        load = jnp.sum(e.reshape(-1)[:, None] == jnp.arange(dm["experts"]),
                       0)
        return h, e, g, load

    @functools.partial(jax.jit, static_argnames=("cap",))
    def experts(x, h, e, g, ex, shared, cap):
        """x + the routed experts' outputs (each expert's tokens gathered
        into a buffer of ``cap`` rows, at least its load) and the shared
        experts'."""
        S, k = e.shape
        E = dm["experts"]
        flat = e.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        load = jnp.sum(flat[:, None] == jnp.arange(E), 0)
        start = jnp.cumsum(load) - load
        slot = start[:, None] + jnp.arange(cap)                # (E, cap)
        ok = jnp.arange(cap)[None] < load[:, None]
        pick = order[jnp.clip(slot, 0, S * k - 1)]
        tok = jnp.where(ok, pick // k, S)                      # S: a pad row
        gate = jnp.where(ok, g.reshape(-1)[pick], 0.0)
        hp = jnp.concatenate([h, jnp.zeros((1, h.shape[1]), h.dtype)], 0)
        G = EXPERT_GROUP

        def group(acc, i):
            sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * G, G, 0)
            wg, wu, wd = (sl(ex["gate"]).astype(jnp.float32),
                          sl(ex["up"]).astype(jnp.float32),
                          sl(ex["down"]).astype(jnp.float32))
            buf = hp[sl(tok)]                                  # (G, cap, d)
            y = _emm("ecf,efd->ecd",
                     jax.nn.silu(_emm("ecd,edf->ecf", buf, wg, lo, 1))
                     * _emm("ecd,edf->ecf", buf, wu, lo, 1), wd, lo, 1)
            y = y * sl(gate)[..., None]
            return acc.at[sl(tok).reshape(-1)].add(
                y.reshape(-1, y.shape[-1])), None

        acc = jnp.zeros(hp.shape, jnp.float32)
        acc, _ = jax.lax.scan(group, acc, jnp.arange(E // G))
        out = x + acc[:S]
        if shared is not None:
            out = out + gated(h, shared["wi_gate"], shared["wi_up"],
                              shared["wo_mlp"])
        return out

    def ffn(x, w):
        """The MoE FFN (``w`` holds the layer's ``moe`` group; experts
        stay in their served type until their group's einsum)."""
        h, e, g, load = route(x, w)
        cap = -(-max(int(jnp.max(load)), 1) // ROWS) * ROWS
        ex = {"gate": w["experts_gate"], "up": w["experts_up"],
              "down": w["experts_down"]}
        return experts(x, h, e, g, ex, w.get("shared"), cap=cap)

    return {"attn": attn, "mlp": mlp, "moe": ffn}


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
    dec_pos = jnp.asarray((L + np.arange(dec_rows)).astype(np.int32))
    dec_enc = jnp.asarray(R.decode_region_ends(pk, L, dec_rows))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][jnp.asarray(toks)])
        stages = params["stages"]
        for si, stage in enumerate(stages):
            st = stage["l0"]
            for li in range(st["norm_attn"].shape[0]):
                one = lambda a: a[li]
                w = {**jax.tree.map(lambda a: f32(a[li]), st["attn"]),
                     "norm_attn": f32(st["norm_attn"][li]),
                     "norm_mlp": f32(st["norm_mlp"][li])}
                x = layer["attn"](x, w, dec_pos, dec_enc)
                if "mlp" in st:
                    w.update(jax.tree.map(lambda a: f32(a[li]), st["mlp"]))
                    x = layer["mlp"](x, w)
                else:
                    moe = st["moe"]
                    wm = {"norm_mlp": w["norm_mlp"],
                          "router": f32(moe["router"][li]),
                          "experts_gate": one(moe["experts_gate"]),
                          "experts_up": one(moe["experts_up"]),
                          "experts_down": one(moe["experts_down"])}
                    if "shared" in moe:
                        wm["shared"] = jax.tree.map(lambda a: f32(a[li]),
                                                    moe["shared"])
                    x = layer["moe"](x, wm)
        xo = R.rms(x[L - 1:L - 1 + T], f32(params["final_norm"]), dm["eps"])
        head = (f32(params["embed"]).T if dm["tied"]
                else f32(params["unembed"]))
        return R.mm(xo, head, lo)
