"""Random weights from the seed, made by the benchmark and not the program.

The tree is the serving engine's parameter layout as the configuration's
architecture file gives it (``layout(mc)`` of ``bench/archs/<arch>.py``:
flat ``path -> (shape, kind)``, layers under ``stages/<i>/l<j>/``). Each
leaf is drawn from the seed and a key of its own path. ``check_layout``
compares the tree with the program's own shapes (``jax.eval_shape`` of
its init, no values), so a renamed or reshaped leaf stops the run
instead of serving garbage. The plain reference regenerates the same
tree from the same seed; it takes no array the program made.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

STD = 0.02        # matrices and biases
NORM_STD = 0.1    # norm scales are 1 + NORM_STD · N(0, 1)


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
    """The served tree of a flat layout; ``stages`` becomes the list of
    stages in the order of their index."""
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = val
    stages = tree["stages"]
    tree["stages"] = [stages[str(i)] for i in range(len(stages))]
    return tree


def served_dtype(mc):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[mc.dtype]


def _specs(lay: dict, mc) -> dict:
    """``path -> (shape, kind, dtype)``: a layout entry ``(shape, kind)``
    is made in the type ``mc`` serves, ``(shape, kind, "float32")`` in
    the type it names (a leaf the program keeps wider, such as a
    router)."""
    out = {}
    for path, (shape, kind, *dt) in lay.items():
        out[path] = (shape, kind,
                     jnp.dtype(dt[0]) if dt else served_dtype(mc))
    return out


def make(lay: dict, mc, seed: int) -> dict:
    """The whole served tree of layout ``lay``, made on the device in one
    jitted call, in the type each leaf is served in."""
    specs = _specs(lay, mc)

    @jax.jit
    def build(key):
        return {p: _leaf(key, p, s, k, dt) for p, (s, k, dt) in specs.items()}

    return _nest(build(seed_key(seed)))


def check_layout(lay: dict, mc, params_shapes) -> None:
    """Raise unless the tree of layout ``lay`` has the program's paths,
    shapes and types (``params_shapes``: the program's init under
    ``jax.eval_shape``)."""
    ours = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: _nest({p: jnp.zeros(s, dt) for p, (s, _, dt)
                                      in _specs(lay, mc).items()})))
    theirs = jax.tree_util.tree_flatten_with_path(params_shapes)
    a = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in ours[0]}
    b = {jax.tree_util.keystr(p): (l.shape, l.dtype) for p, l in theirs[0]}
    if a != b:
        raise SystemExit(f"bench: the program's parameter layout changed: "
                         f"only ours {sorted(set(a) - set(b))}, only the "
                         f"program's {sorted(set(b) - set(a))}, differing "
                         f"{sorted(k for k in a if k in b and a[k] != b[k])}")
