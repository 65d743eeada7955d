"""Mixture-of-Experts FFN: dropless top-k routing + shared experts.

Covers grok-1 (8 experts, top-2, renormalised gates) and deepseek-v2-lite
(64 routed top-6 + 2 shared, softmax gates used as they are:
``norm_topk_prob=False``). Dispatch is **dropless** sort-and-segment: the
n·k (token, slot) assignments are sorted by expert, each expert's
contiguous segment runs through its weights as one grouped matmul
(``jax.lax.ragged_dot``), and the outputs are unsorted back by the
inverse permutation and summed with the gates. Every assignment is
computed, whatever the batch's load per expert, so a token's expert
output never depends on its batch-mates (a capacity buffer would drop
the overflow of a busy expert). Memory is O(n·k·(d + d_ff)); compiled
FLOPs are the active experts' (honest MoE rooflines).

The router aux (load-balance) loss follows Shazeer et al.: E · Σ_e f_e·p_e.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.models.layers import init_mlp, mlp_fwd, truncated_normal


def init_moe(key, d_model: int, d_ff: int, num_experts: int,
             num_shared: int, dtype) -> dict:
    ks = jax.random.split(key, 5)
    p = {
        "router": truncated_normal(ks[0], (d_model, num_experts), std=0.006
                                   ).astype(jnp.float32),
        "experts_gate": truncated_normal(ks[1], (num_experts, d_model, d_ff)
                                         ).astype(dtype),
        "experts_up": truncated_normal(ks[2], (num_experts, d_model, d_ff)
                                       ).astype(dtype),
        "experts_down": truncated_normal(ks[3], (num_experts, d_ff, d_model)
                                         ).astype(dtype),
    }
    if num_shared:
        p["shared"] = init_mlp(ks[4], d_model, num_shared * d_ff, dtype)
    return p


def _route(p, xt, top_k: int, norm_topk_prob: bool):
    """Softmax router → (probs (n, E), gates (n, k), experts (n, k)). The
    gates are the top-k probabilities, renormalised to sum to 1 only
    with ``norm_topk_prob``."""
    logits = xt.astype(jnp.float32) @ p["router"]           # (n, E)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)     # (n, k)
    if norm_topk_prob:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_idx


def _experts(p, xt, gate_vals, expert_idx):
    """Dropless routed-expert FFN. xt (n, d) → (n, d)."""
    n, d = xt.shape
    E = p["router"].shape[1]
    k = expert_idx.shape[1]
    with jax.named_scope(phases.MOE_ROUTE):
        flat = expert_idx.reshape(-1)                          # (n·k,)
        order = jnp.argsort(flat, stable=True)                 # by expert
        inverse = jnp.argsort(order)
        sizes = jnp.sum(flat[None, :] == jnp.arange(E)[:, None], axis=1,
                        dtype=jnp.int32)                       # (E,)
    with jax.named_scope(phases.MOE_EXPERTS):
        xs = xt[order // k]                                    # (n·k, d)
        gate = jax.lax.ragged_dot(xs, p["experts_gate"], sizes)
        up = jax.lax.ragged_dot(xs, p["experts_up"], sizes)
        h = jax.nn.silu(gate) * up
        ys = jax.lax.ragged_dot(h, p["experts_down"], sizes)  # (n·k, d)
        y = ys[inverse].reshape(n, k, d).astype(jnp.float32)
        return jnp.einsum("nkd,nk->nd", y, gate_vals)


def _shared(p, x):
    with jax.named_scope(phases.MOE_SHARED):
        return mlp_fwd(p["shared"], x)


def moe_fwd(p: dict, x: jax.Array, top_k: int, norm_topk_prob: bool = True
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (b, s, d) → (out, aux_loss). Dropless: every token reaches all
    of its top-k experts."""
    b, s, d = x.shape
    E = p["router"].shape[1]
    xt = x.reshape(b * s, d)
    with jax.named_scope(phases.MOE_ROUTE):
        probs, gate_vals, expert_idx = _route(p, xt, top_k, norm_topk_prob)
    out = _experts(p, xt, gate_vals, expert_idx).reshape(b, s, d)
    if "shared" in p:
        out = out + _shared(p, x).astype(out.dtype)

    # load-balance aux loss
    density = jax.nn.one_hot(expert_idx[:, 0], E).mean(0)
    router_prob = probs.mean(0)
    aux = E * jnp.sum(density * router_prob)
    return out.astype(x.dtype), aux


def moe_decode(p: dict, x_t: jax.Array, top_k: int,
               norm_topk_prob: bool = True) -> jax.Array:
    """Decode path: ``moe_fwd``'s dispatch over the b decoded tokens,
    (b, d) → (b, d): the grouped matmuls hold one row per (token, slot)."""
    with jax.named_scope(phases.MOE_ROUTE):
        _, gate_vals, expert_idx = _route(p, x_t, top_k, norm_topk_prob)
    out = _experts(p, x_t, gate_vals, expert_idx)
    if "shared" in p:
        out = out + _shared(p, x_t).astype(out.dtype)
    return out.astype(x_t.dtype)
