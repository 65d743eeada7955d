"""Composable LM assembly: config → layer plan → init / train / prefill / decode.

A model is a sequence of **stages**; each stage is a *period* of LayerDefs
scanned ``repeat`` times with stacked parameters (compile-time friendly for
46–64-layer archs, and naturally expresses repeating local/global or
self/cross patterns: gemma3 = period of 5 local + 1 global, llama-vision =
4 self + 1 cross, …).

Decode integrates ParisKV per DESIGN.md §4: global-attention layers carry a
LayerKVCache + metadata and retrieve Top-k; sliding-window layers carry a
ring buffer of window size; SSM layers carry O(1) recurrent state; MLA
carries the latent cache. `decode` and `prefill` drive the Sink/Retrieval/
Local/Update regions of core.cache.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.layers import AttnSpec


# ----------------------------------------------------------- layer plan ----
@dataclasses.dataclass(frozen=True)
class LayerDef:
    mixer: str                 # 'attn' | 'cross' | 'ssm' | 'hybrid' | 'mla'
    attn: Optional[AttnSpec] = None
    ffn: str = "mlp"           # 'mlp' | 'moe' | 'none'
    d_ff: int = 0
    cross: bool = False        # extra cross-attn sublayer (whisper decoder)
    use_pariskv: bool = True   # retrieval at decode (False → dense/ring)


@dataclasses.dataclass(frozen=True)
class StageDef:
    layers: Tuple[LayerDef, ...]
    repeat: int


def _attn_spec(cfg: ModelConfig, sliding: int = 0, causal: bool = True,
               qk_norm: bool = False) -> AttnSpec:
    scale = 0.0
    if cfg.query_pre_attn_scalar:
        scale = cfg.query_pre_attn_scalar ** -0.5
    return AttnSpec(
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
        qkv_bias=cfg.qkv_bias, softcap=cfg.attn_logit_softcap,
        sliding_window=sliding, qk_norm=qk_norm, sm_scale=scale,
        causal=causal)


def layer_plan(cfg: ModelConfig) -> Tuple[StageDef, ...]:
    """Derive the stage/period structure from a ModelConfig."""
    f = cfg.family
    if f == "ssm":
        return (StageDef((LayerDef("ssm", ffn="none"),), cfg.num_layers),)
    if f == "hybrid":
        ld = LayerDef("hybrid", _attn_spec(cfg), ffn="mlp", d_ff=cfg.d_ff)
        return (StageDef((ld,), cfg.num_layers),)
    if f == "moe" and cfg.kv_lora_rank:        # deepseek-v2 family
        dense = LayerDef("mla", None, ffn="mlp",
                         d_ff=cfg.first_dense_d_ff or cfg.d_ff)
        moe_l = LayerDef("mla", None, ffn="moe", d_ff=cfg.moe_d_ff or cfg.d_ff)
        stages = []
        if cfg.first_dense_layers:
            stages.append(StageDef((dense,), cfg.first_dense_layers))
        stages.append(StageDef((moe_l,),
                               cfg.num_layers - cfg.first_dense_layers))
        return tuple(stages)
    if f == "moe":                              # grok-1
        ld = LayerDef("attn", _attn_spec(cfg), ffn="moe",
                      d_ff=cfg.moe_d_ff or cfg.d_ff)
        return (StageDef((ld,), cfg.num_layers),)
    if f == "vlm":                              # llama-3.2-vision
        period = cfg.cross_attn_period
        self_l = LayerDef("attn", _attn_spec(cfg), ffn="mlp", d_ff=cfg.d_ff)
        cross_l = LayerDef("cross", _attn_spec(cfg, causal=False), ffn="mlp",
                           d_ff=cfg.d_ff, use_pariskv=False)
        layers = (self_l,) * (period - 1) + (cross_l,)
        return (StageDef(layers, cfg.num_layers // period),)
    if f == "audio":                            # whisper decoder (+cross)
        ld = LayerDef("attn", _attn_spec(cfg), ffn="mlp", d_ff=cfg.d_ff,
                      cross=True)
        return (StageDef((ld,), cfg.num_layers),)
    # dense family, possibly local/global alternating
    if cfg.local_global_period:
        p = cfg.local_global_period
        qk = cfg.name.startswith("gemma3")
        local = LayerDef("attn", _attn_spec(cfg, sliding=cfg.sliding_window,
                                            qk_norm=qk),
                         ffn="mlp", d_ff=cfg.d_ff, use_pariskv=False)
        glob = LayerDef("attn", _attn_spec(cfg, qk_norm=qk), ffn="mlp",
                        d_ff=cfg.d_ff)
        layers = (local,) * (p - 1) + (glob,)
        return (StageDef(layers, cfg.num_layers // p),)
    ld = LayerDef("attn", _attn_spec(cfg), ffn="mlp", d_ff=cfg.d_ff)
    return (StageDef((ld,), cfg.num_layers),)


# ------------------------------------------------------------------ init ----
def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


def init_layer(key, cfg: ModelConfig, ld: LayerDef) -> dict:
    dt = _dtype(cfg)
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm_attn": jnp.ones((cfg.d_model,), dt)}
    if ld.mixer in ("attn", "hybrid"):
        p["attn"] = L.init_attn(ks[0], cfg.d_model, ld.attn, dt)
    elif ld.mixer == "cross":
        p["attn"] = L.init_attn(ks[0], cfg.d_model, ld.attn, dt)
        p["cross_gate"] = jnp.zeros((), dt)
    elif ld.mixer == "mla":
        p["attn"] = MLA.init_mla(ks[0], cfg, dt)
    if ld.mixer in ("ssm", "hybrid"):
        p["ssm"] = SSM.init_ssm(ks[1], cfg, dt)
    if ld.cross:
        p["cross"] = L.init_attn(ks[2], cfg.d_model, ld.attn, dt)
        p["norm_cross"] = jnp.ones((cfg.d_model,), dt)
    if ld.ffn != "none":
        p["norm_mlp"] = jnp.ones((cfg.d_model,), dt)
        if ld.ffn == "moe":
            p["moe"] = MOE.init_moe(ks[3], cfg.d_model, ld.d_ff,
                                    cfg.num_experts, cfg.num_shared_experts, dt)
        else:
            p["mlp"] = L.init_mlp(ks[4], cfg.d_model, ld.d_ff, dt)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dt = _dtype(cfg)
    plan = layer_plan(cfg)
    key, k_emb, k_enc = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": L.truncated_normal(k_emb, (cfg.vocab_size, cfg.d_model)
                                    ).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "stages": [],
    }
    if not cfg.tie_embeddings:
        key, k_un = jax.random.split(key)
        params["unembed"] = L.truncated_normal(
            k_un, (cfg.d_model, cfg.vocab_size)).astype(dt)
    for stage in plan:
        key, sk = jax.random.split(key)
        reps = jax.random.split(sk, stage.repeat)

        def one_rep(rk):
            lks = jax.random.split(rk, len(stage.layers))
            return {f"l{i}": init_layer(lks[i], cfg, ld)
                    for i, ld in enumerate(stage.layers)}

        stacked = jax.vmap(one_rep)(reps)
        params["stages"].append(stacked)
    if cfg.encoder_layers:  # whisper encoder
        spec = _attn_spec(cfg, causal=False)
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers)

        def enc_rep(rk):
            a, b = jax.random.split(rk)
            return {"norm_attn": jnp.ones((cfg.d_model,), dt),
                    "attn": L.init_attn(a, cfg.d_model, spec, dt),
                    "norm_mlp": jnp.ones((cfg.d_model,), dt),
                    "mlp": L.init_mlp(b, cfg.d_model, cfg.d_ff, dt)}

        params["encoder"] = jax.vmap(enc_rep)(enc_keys)
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dt)
    return params


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ----------------------------------------------------------- train fwd ----
def _embed(params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embed_by_sqrt_d:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x


def _unembed(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["unembed"]
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(
            logits / cfg.final_logit_softcap)
    return logits


def layer_fwd_train(p: dict, x: jax.Array, ld: LayerDef, cfg: ModelConfig,
                    positions: jax.Array, media: Optional[jax.Array]
                    ) -> Tuple[jax.Array, jax.Array]:
    """One layer, full-sequence (train/prefill-without-cache). → (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["norm_attn"], cfg.norm_eps)
    if ld.mixer == "attn":
        y = L.attn_train(p["attn"], h, ld.attn, positions)
    elif ld.mixer == "mla":
        y = MLA.mla_train(p["attn"], h, cfg, positions)
    elif ld.mixer == "cross":
        y = L.attn_cross(p["attn"], h, media, ld.attn)
        y = jnp.tanh(p["cross_gate"]) * y
    elif ld.mixer == "ssm":
        y = SSM.ssm_train(p["ssm"], h, cfg)
    elif ld.mixer == "hybrid":
        y = 0.5 * (L.attn_train(p["attn"], h, ld.attn, positions)
                   + SSM.ssm_train(p["ssm"], h, cfg))
    else:
        raise ValueError(ld.mixer)
    x = x + y.astype(x.dtype)
    if ld.cross:
        h = L.rms_norm(x, p["norm_cross"], cfg.norm_eps)
        x = x + L.attn_cross(p["cross"], h, media, ld.attn).astype(x.dtype)
    if ld.ffn != "none":
        h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        if ld.ffn == "moe":
            y, aux = MOE.moe_fwd(p["moe"], h, cfg.experts_per_token,
                                 cfg.norm_topk_prob)
        else:
            y = L.mlp_fwd(p["mlp"], h)
        x = x + y.astype(x.dtype)
    return x, aux


def encoder_fwd(params, cfg: ModelConfig, feats: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (b, T, d)."""
    pos = jnp.asarray(L.sinusoidal_positions(feats.shape[1], cfg.d_model))
    x = feats + pos[None].astype(feats.dtype)
    spec = _attn_spec(cfg, causal=False)

    def body(x, p):
        h = L.rms_norm(x, p["norm_attn"], cfg.norm_eps)
        x = x + L.attn_encoder(p["attn"], h, spec)
        h = L.rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        return x + L.mlp_fwd(p["mlp"], h), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return L.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)


def forward_train(params, cfg: ModelConfig, tokens: jax.Array,
                  media: Optional[jax.Array] = None,
                  remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """tokens (b, s) → (logits (b, s, v), aux_loss). ``media`` carries the
    stub image-patch / audio-frame embeddings for vlm/audio archs."""
    b, s = tokens.shape
    x = _embed(params, cfg, tokens)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if cfg.family == "audio":
        media = encoder_fwd(params, cfg, media)
    aux_total = jnp.zeros((), jnp.float32)
    for stage, sp in zip(layer_plan(cfg), params["stages"]):

        def body(carry, p_slice):
            x, aux = carry
            for i, ld in enumerate(stage.layers):
                fwd = layer_fwd_train
                if remat:
                    fwd = jax.checkpoint(
                        functools.partial(layer_fwd_train, ld=ld, cfg=cfg),
                        static_argnums=())
                    y, a = fwd(p_slice[f"l{i}"], x, positions=positions,
                               media=media)
                else:
                    y, a = fwd(p_slice[f"l{i}"], x, ld, cfg, positions, media)
                x, aux = y, aux + a
            return (x, aux), None

        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), sp)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _unembed(params, cfg, x), aux_total
