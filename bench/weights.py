"""Random weights from the seed, made by the benchmark and not the program.

The tree is the serving engine's parameter layout for a dense
attention + gated-MLP model. ``check_layout`` compares it with the
program's own shapes (``jax.eval_shape`` of its init, no values), so a
renamed or reshaped leaf stops the run instead of serving garbage. The
plain reference regenerates the same tree from the same seed; it takes
no array the program made.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

STD = 0.02        # matrices and biases
NORM_STD = 0.1    # norm scales are 1 + NORM_STD · N(0, 1)


def layout(mc) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """Flat ``path -> (shape, kind)`` of the served tree; ``kind`` is
    ``matrix``, ``bias`` or ``norm``. Layer leaves are stacked on a
    leading axis of ``num_layers``."""
    d, f, v = mc.d_model, mc.d_ff, mc.vocab_size
    H, G, hd, R = mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.num_layers
    lay = {
        "embed": ((v, d), "matrix"),
        "final_norm": ((d,), "norm"),
        "stages/0/l0/norm_attn": ((R, d), "norm"),
        "stages/0/l0/norm_mlp": ((R, d), "norm"),
        "stages/0/l0/attn/wq": ((R, d, H * hd), "matrix"),
        "stages/0/l0/attn/wk": ((R, d, G * hd), "matrix"),
        "stages/0/l0/attn/wv": ((R, d, G * hd), "matrix"),
        "stages/0/l0/attn/wo": ((R, H * hd, d), "matrix"),
        "stages/0/l0/mlp/wi_gate": ((R, d, f), "matrix"),
        "stages/0/l0/mlp/wi_up": ((R, d, f), "matrix"),
        "stages/0/l0/mlp/wo_mlp": ((R, f, d), "matrix"),
    }
    if mc.qkv_bias:
        lay["stages/0/l0/attn/bq"] = ((R, H * hd), "bias")
        lay["stages/0/l0/attn/bk"] = ((R, G * hd), "bias")
        lay["stages/0/l0/attn/bv"] = ((R, G * hd), "bias")
    if not mc.tie_embeddings:
        lay["unembed"] = ((d, v), "matrix")
    return lay


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.PRNGKey(0)
    for shift in (0, 32, 64):
        key = jax.random.fold_in(key, (seed >> shift) & 0xFFFFFFFF)
    return key


def _leaf(key, path: str, shape, kind: str, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "norm":
        return (1.0 + NORM_STD * z).astype(dtype)
    return (STD * z).astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    tree["stages"] = [tree["stages"]["0"]]
    return tree


def served_dtype(mc):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[mc.dtype]


def make(mc, seed: int) -> dict:
    """The whole served tree, made on the device in one jitted call, in
    the type it is served in."""
    lay = layout(mc)
    dtype = served_dtype(mc)

    @jax.jit
    def build(key):
        return {p: _leaf(key, p, s, k, dtype) for p, (s, k) in lay.items()}

    return _nest(build(seed_key(seed)))


def check_layout(mc, params_shapes) -> None:
    """Raise unless the benchmark's tree has the program's paths and shapes
    (``params_shapes``: the program's init under ``jax.eval_shape``)."""
    ours = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: _nest({p: jnp.zeros(s, served_dtype(mc))
                                      for p, (s, _) in layout(mc).items()})))
    theirs = jax.tree_util.tree_flatten_with_path(params_shapes)
    a = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in ours[0]}
    b = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in theirs[0]}
    if a != b:
        raise SystemExit(f"bench: the program's parameter layout changed: "
                         f"only ours {sorted(set(a) - set(b))}, only the "
                         f"program's {sorted(set(b) - set(a))}, differing "
                         f"{sorted(k for k in a if k in b and a[k] != b[k])}")
