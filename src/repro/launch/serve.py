"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Runs one of the serving engines over synthetic prompts and reports the
paper's efficiency metrics — per-request TTFT, TPOT, and aggregate
decode throughput — for ParisKV vs the full-attention baseline:

* ``--engine slots`` (default): slot-based continuous batching over
  contiguous per-slot caches (``ServingEngine``);
* ``--engine wave``: the legacy lockstep ``WaveServingEngine``;
* ``--engine paged``: the main serving path, ``PagedServingEngine`` with
  fused two-stage retrieval over a global block pool (``--block-size``,
  ``--prefill-budget`` for chunked prefill, ``--offload`` for the tiered
  host-offloaded pool).

``build_engine`` is the one place engines are constructed from these
settings; ``chip_smoke.py`` builds its engines through it too.
"""
from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Optional

import jax

from repro import configs
from repro.data import SyntheticLMStream, media_stub
from repro.models import model as M
from repro.serving import (PagedServingEngine, Request, ServingEngine,
                           WaveServingEngine)

ENGINES = ("slots", "wave", "paged")

# <repo root>/.jax_cache — a fixed path in the checkout, never one made
# from a tmpdir, pid or time, so every run from this checkout finds what
# the earlier ones compiled
_REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself, nothing is set here), else ``<repo root>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)


def build_engine(cfg, params, *, engine: str = "slots", n_max: int = 512,
                 max_batch: int = 4, chunk_size: int = 8,
                 use_pariskv: bool = True, block_size: int = 128,
                 prefill_budget: int = 0, offload: bool = False,
                 num_device_blocks: Optional[int] = None,
                 mesh_shards: int = 1):
    """Construct a serving engine from launcher settings.

    ``engine="paged"`` always serves ParisKV with fused retrieval
    (``use_pariskv`` must stay True); ``offload``, ``num_device_blocks``
    and ``mesh_shards`` apply to it only."""
    if engine not in ENGINES:
        raise ValueError(f"engine must be one of {ENGINES}, got {engine!r}")
    if engine != "paged" and (offload or mesh_shards > 1):
        raise ValueError("offload and mesh_shards need engine='paged'")
    if engine == "wave":
        return WaveServingEngine(cfg, params, n_max=n_max,
                                 max_batch=max_batch,
                                 use_pariskv=use_pariskv)
    if engine == "slots":
        return ServingEngine(cfg, params, n_max=n_max, max_batch=max_batch,
                             chunk_size=chunk_size, use_pariskv=use_pariskv,
                             prefill_budget=prefill_budget)
    if not use_pariskv:
        raise ValueError("the paged engine serves the ParisKV path only")
    kw = dict(offload=True, num_device_blocks=num_device_blocks) \
        if offload else {}
    return PagedServingEngine(cfg, params, n_max=n_max, max_batch=max_batch,
                              block_size=block_size, chunk_size=chunk_size,
                              prefill_budget=prefill_budget,
                              mesh_shards=mesh_shards, **kw)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=ENGINES, default="slots")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=192)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--n-max", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode steps per host sync (slot/paged engines)")
    ap.add_argument("--block-size", type=int, default=128,
                    help="paged: tokens per pool block")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prompt tokens per mixed prefill+decode step "
                         "(0 = solo prefill at admission)")
    ap.add_argument("--offload", action="store_true",
                    help="paged: K/V blocks in host memory, device keeps "
                         "metadata + a staging pool of a quarter of them")
    ap.add_argument("--baseline", action="store_true",
                    help="full attention instead of ParisKV")
    args = ap.parse_args()
    if args.engine != "paged" and args.offload:
        ap.error("--offload requires --engine paged")

    enable_compile_cache()
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = build_engine(cfg, params, engine=args.engine, n_max=args.n_max,
                          max_batch=args.batch, chunk_size=args.chunk,
                          use_pariskv=not args.baseline,
                          block_size=args.block_size,
                          prefill_budget=args.prefill_budget,
                          offload=args.offload)
    stream = SyntheticLMStream(cfg.vocab_size, seed=1)
    media = None
    if cfg.family == "vlm":
        media = media_stub(1, cfg.num_media_tokens, cfg.d_model)[0]
    if cfg.family == "audio":
        media = media_stub(1, cfg.encoder_seq, cfg.d_model)[0]
    for i in range(args.requests):
        engine.submit(Request(uid=i, prompt=stream.sequence(args.prompt_len),
                              max_new_tokens=args.gen, media=media))
    t0 = time.perf_counter()
    try:
        done = engine.run()
    finally:
        if isinstance(engine, ServingEngine):
            engine.close()        # offload: joins the fetch workers
    wall = time.perf_counter() - t0
    for r in done:
        tpot = r.decode_s / max(r.max_new_tokens - 1, 1) * 1000
        print(f"req {r.uid}: ttft {r.ttft_s*1000:.1f}ms  "
              f"tpot {tpot:.1f}ms/tok  out[:8]={r.output[:8].tolist()}")
    mode = "full-attention" if args.baseline else "ParisKV"
    agg = sum(len(r.output) for r in done) / max(wall, 1e-9)
    print(f"[{mode}/{args.engine}] end-to-end throughput ≈ {agg:.1f} tok/s "
          f"({len(done)} requests in {wall:.2f}s)")


if __name__ == "__main__":
    main()
