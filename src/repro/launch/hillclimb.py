import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimb experiments (EXPERIMENTS.md §Perf).

Three pairs from the baseline roofline table:

  E1  llama-3.2-vision-11b × decode_32k   (most collective-bound decode)
  E2  stablelm-1.6b × long_500k           (worst collective/memory ratio)
  E3  gemma2-27b × train_4k               (memory-bound, biggest dense train)

E1/E2 isolate ONE global-attention layer's decode step and compare, on the
production mesh, baseline GSPMD retrieval against a **shard_map distributed
retrieval** (beyond-paper): each sequence shard scores its local metadata,
takes a local top-k, all-gathers only the (tiny) per-shard winners, and
contributes its owned K/V rows by masked-gather + psum — replacing XLA's
all-gather-the-cache lowering of the global gather.

E3 A/Bs whole-model knobs through the dryrun machinery: remat policy and
pure-TP vs FSDP×TP parameter sharding.

Usage: python -m repro.launch.hillclimb [--exp e1|e2|e3|all]
"""
import argparse
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import encode as E
from repro.core import retrieval as R
from repro.core.config import ParisKVConfig
from repro.launch import mesh as MX
from repro.launch.dryrun import _cost_of


# ---------------------------------------------------------------- helpers --
def report(tag: str, cost: Dict[str, float], layers: int = 1) -> Dict:
    t_m = cost["bytes"] * layers / 819e9 * 1e3
    t_c = cost["coll"] * layers / 50e9 * 1e3
    print(f"{tag:42s} bytes/dev={cost['bytes']*layers/1e9:8.2f} GB "
          f"coll/dev={cost['coll']*layers/1e9:8.2f} GB  "
          f"t_mem={t_m:8.1f} ms  t_coll={t_c:8.1f} ms", flush=True)
    return dict(tag=tag, **{k: v * layers for k, v in cost.items()},
                t_mem_ms=t_m, t_coll_ms=t_c)


def _specs(batch, n, G, Hg, hd, Bsub, dt=jnp.bfloat16):
    sds = jax.ShapeDtypeStruct
    return dict(
        q=sds((batch, G, Hg, hd), jnp.float32),
        k_cache=sds((batch, n, G, hd), dt),
        v_cache=sds((batch, n, G, hd), dt),
        ids=sds((batch, G, n, Bsub), jnp.uint8),
        codes=sds((batch, G, n, Bsub), jnp.uint32),
        w=sds((batch, G, n, Bsub), jnp.float32),
    )


def one_layer_decode_baseline(cfg, pcfg: ParisKVConfig, mesh, batch, n,
                              seq_axes, batch_axes):
    """Baseline: pure GSPMD — global retrieve + global gather."""
    G, H, hd = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    Hg = H // G
    Bsub = pcfg.num_subspaces(hd)
    C = pcfg.candidate_count(n)
    from repro.models import serve as SV
    signs = SV.rotation_signs(cfg)

    def step(q, k_cache, v_cache, ids, codes, w):
        meta = E.KeyMetadata(ids[:, :, None], codes[:, :, None],
                             w[:, :, None])
        qt = E.encode_query(q, pcfg, signs)
        valid = jnp.ones((q.shape[0], G, 1, n), bool)
        res = R.retrieve(meta, qt, valid, pcfg, C, pcfg.top_k)
        from repro.core.attention import gather_kv_heads
        k_sel = gather_kv_heads(k_cache, res.indices)
        v_sel = gather_kv_heads(v_cache, res.indices)
        s = jnp.einsum("bghd,bghkd->bghk", q, k_sel.astype(jnp.float32))
        p = jax.nn.softmax(s * hd ** -0.5, -1)
        return jnp.einsum("bghk,bghkd->bghd", p, v_sel.astype(jnp.float32))

    sp = _specs(batch, n, G, Hg, hd, Bsub)
    sh = dict(
        q=NamedSharding(mesh, P(batch_axes, None, None, None)),
        k_cache=NamedSharding(mesh, P(batch_axes, seq_axes, None, None)),
        v_cache=NamedSharding(mesh, P(batch_axes, seq_axes, None, None)),
        ids=NamedSharding(mesh, P(batch_axes, None, seq_axes, None)),
        codes=NamedSharding(mesh, P(batch_axes, None, seq_axes, None)),
        w=NamedSharding(mesh, P(batch_axes, None, seq_axes, None)),
    )
    with mesh:
        lowered = jax.jit(step, in_shardings=tuple(
            sh[k] for k in ("q", "k_cache", "v_cache", "ids", "codes", "w"))
        ).lower(*(sp[k] for k in ("q", "k_cache", "v_cache", "ids",
                                  "codes", "w")))
        return _cost_of(lowered)


def one_layer_decode_shardmap(cfg, pcfg: ParisKVConfig, mesh, batch, n,
                              seq_axes, batch_axes):
    """Optimized: shard_map hierarchical retrieval + psum row fetch.

    Each sequence shard: local collision scores → local top-k → all-gather
    the (k × n_shards) candidate estimates (tiny) → global top-k indices →
    every shard contributes its owned K/V rows via masked local gather +
    psum. Collectives: O(k·shards·4B) gather + O(b·G·Hg·k·hd) psum — vs the
    baseline's cache-scale all-gathers.
    """
    G, H, hd = cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    Hg = H // G
    Bsub = pcfg.num_subspaces(hd)
    from repro.models import serve as SV
    signs = SV.rotation_signs(cfg)
    seq_tuple = seq_axes if isinstance(seq_axes, tuple) else (seq_axes,)
    n_shards = int(np.prod([mesh.shape[a] for a in seq_tuple]))
    n_loc = n // n_shards
    k_top = pcfg.top_k
    C_loc = min(pcfg.candidate_count(n_loc), n_loc)

    def local_step(q, k_cache, v_cache, ids, codes, w):
        # block-local shapes: q (b_l, G, Hg, hd) replicated over seq axes;
        # cache (b_l, n_loc, G, hd); metadata (b_l, G, n_loc, B)
        axis_idx = jax.lax.axis_index(seq_tuple)
        base = axis_idx * n_loc
        meta = E.KeyMetadata(ids[:, :, None], codes[:, :, None],
                             w[:, :, None])
        qt = E.encode_query(q, pcfg, signs)
        valid = jnp.ones((q.shape[0], G, 1, n_loc), bool)
        res = R.retrieve(meta, qt, valid, pcfg, C_loc, k_top)
        # all-gather per-shard winners: (shards, b, G, Hg, k) scores+indices
        glob_idx = res.indices + base
        all_scores = jax.lax.all_gather(res.scores, seq_tuple)
        all_idx = jax.lax.all_gather(glob_idx, seq_tuple)
        all_scores = all_scores.reshape((-1,) + res.scores.shape[1:][:-1]
                                        + (n_shards * k_top,)) \
            if False else jnp.moveaxis(all_scores, 0, -2).reshape(
                res.scores.shape[:-1] + (n_shards * k_top,))
        all_idx = jnp.moveaxis(all_idx, 0, -2).reshape(
            glob_idx.shape[:-1] + (n_shards * k_top,))
        _, pos = jax.lax.top_k(all_scores, k_top)
        final_idx = jnp.take_along_axis(all_idx, pos, -1)  # global positions

        # masked local contribution + psum
        local = final_idx - base
        mine = (local >= 0) & (local < n_loc)
        safe = jnp.clip(local, 0, n_loc - 1)
        from repro.core.attention import gather_kv_heads
        k_rows = gather_kv_heads(k_cache, safe) * mine[..., None]
        v_rows = gather_kv_heads(v_cache, safe) * mine[..., None]
        k_sel = jax.lax.psum(k_rows.astype(jnp.float32), seq_tuple)
        v_sel = jax.lax.psum(v_rows.astype(jnp.float32), seq_tuple)
        s = jnp.einsum("bghd,bghkd->bghk", q, k_sel)
        p = jax.nn.softmax(s * hd ** -0.5, -1)
        return jnp.einsum("bghk,bghkd->bghd", p, v_sel)

    sp = _specs(batch, n, G, Hg, hd, Bsub)
    in_specs = (P(batch_axes, None, None, None),
                P(batch_axes, seq_axes, None, None),
                P(batch_axes, seq_axes, None, None),
                P(batch_axes, None, seq_axes, None),
                P(batch_axes, None, seq_axes, None),
                P(batch_axes, None, seq_axes, None))
    out_spec = P(batch_axes, None, None, None)
    fn = jax.shard_map(local_step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    with mesh:
        lowered = jax.jit(fn).lower(*(sp[k] for k in (
            "q", "k_cache", "v_cache", "ids", "codes", "w")))
        return _cost_of(lowered)


def e1(results):
    print("\n=== E1: llama-3.2-vision-11b × decode_32k (collective-bound) ===")
    cfg = configs.get("llama-3.2-vision-11b")
    pcfg = cfg.pariskv
    mesh = MX.make_production_mesh()
    n, batch, layers = 32_768, 128, 30  # 30 self-attn ParisKV layers
    base = one_layer_decode_baseline(cfg, pcfg, mesh, batch, n,
                                     "model", "data")
    results.append(report("e1/baseline GSPMD (×30 layers)", base, layers))
    opt = one_layer_decode_shardmap(cfg, pcfg, mesh, batch, n,
                                    "model", "data")
    results.append(report("e1/shard_map distributed (×30)", opt, layers))


def e2(results):
    print("\n=== E2: stablelm-1.6b × long_500k (collective/memory worst) ===")
    cfg = configs.get("stablelm-1.6b")
    pcfg = cfg.pariskv
    mesh = MX.make_production_mesh()
    n, batch, layers = 524_288, 1, 24
    base = one_layer_decode_baseline(cfg, pcfg, mesh, batch, n,
                                     ("data", "model"), None)
    results.append(report("e2/baseline GSPMD (×24 layers)", base, layers))
    opt = one_layer_decode_shardmap(cfg, pcfg, mesh, batch, n,
                                    ("data", "model"), None)
    results.append(report("e2/shard_map distributed (×24)", opt, layers))


def e3(results):
    print("\n=== E3: gemma2-27b × train_4k (memory-bound) ===")
    from repro.launch.dryrun import lower_combo
    for tag, env in [("e3/baseline FSDPxTP+remat", {}),
                     ("e3/pure TP (no FSDP)", {"REPRO_FSDP": "0"})]:
        for k, v in env.items():
            os.environ[k] = v
        try:
            rec = lower_combo("gemma2-27b", "train_4k", multi_pod=False)
            cost = dict(flops=rec["flops"], bytes=rec["bytes_accessed"],
                        coll=rec["collectives_compiled"]["total"])
            results.append(report(tag, cost))
        finally:
            for k in env:
                os.environ.pop(k, None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default="all")
    ap.add_argument("--out", default="hillclimb_results.json")
    args = ap.parse_args()
    results = []
    if args.exp in ("e1", "all"):
        e1(results)
    if args.exp in ("e2", "all"):
        e2(results)
    if args.exp in ("e3", "all"):
        e3(results)
    json.dump(results, open(args.out, "w"), indent=1)
    print("→", args.out)


if __name__ == "__main__":
    main()
