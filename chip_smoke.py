#!/usr/bin/env python3
"""On-chip smoke test: paged ParisKV serving end to end on a TPU.

    python chip_smoke.py            # one chip, phases (a)-(d)
    python chip_smoke.py --mesh 4   # four chips, the mesh phase only

Everything runs in this one process (a chip belongs to one process).
Engines are built through ``repro.launch.serve.build_engine``, the
launcher's own constructor; weights are random, from ``PRNGKey(0)``.

(a) device   — the first device must be a TPU, and REPRO_PALLAS_INTERPRET
               must be unset (it would swap the Pallas kernels for their
               jnp twins).
(b) resident — full-width Qwen2-1.5B on ``PagedServingEngine`` (fused
               retrieval, chunked prefill of 512 tokens per step): four
               seeded prompts of 2048-6144 tokens, 64 new tokens each. The
               compiled decode chunk must contain the Pallas kernels
               (``tpu_custom_call``).
(c) offload  — the same requests on the host-offloaded engine with a
               staging pool of a quarter of the blocks; its greedy tokens
               must equal (b)'s.
(d) kernels  — ``retrieve_paged_fused`` with the Pallas kernels against
               its jnp twins on layer 0's pool, block tables and bucket
               histogram, captured from (b) while all four requests
               decode: equal Stage-I scores and candidates, Stage-II
               estimates within ``EST_RTOL`` of the largest, equal winners
               apart from ties at the k-th estimate.

``--mesh N`` runs only the mesh phase: full-width StableLM-1.6B served
with ``mesh_shards=N`` against ``mesh_shards=1`` on one device, same
requests. Tokens must be identical and each device must hold 1/N of
every pool K/V leaf.

A failed, quarantined or degraded request, a fetch timeout, a short
output or any exception exits non-zero. Only when every phase passed is
the last line of stdout ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import encode_query, retrieve_paged_fused  # noqa: E402
from repro.core import retrieval as R  # noqa: E402
from repro.data import SyntheticLMStream  # noqa: E402
from repro.kernels.rerank import rerank_paged_kernel  # noqa: E402
from repro.launch.serve import build_engine, enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import serve as SV  # noqa: E402
from repro.serving import Request  # noqa: E402

# Stage-II estimates: kernel vs twin, relative to the largest |estimate|
# of the row set (float32 sums over B·m terms in different orders)
EST_RTOL = 1e-4


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    arch: str
    n_max: int
    max_batch: int
    prompt_lens: tuple
    max_new_tokens: int
    block_size: int = 128
    chunk_size: int = 8
    prefill_budget: int = 512


ONE_CHIP = ServeSpec("qwen2-1.5b", n_max=8192, max_batch=4,
                     prompt_lens=(2048, 3072, 4096, 6144), max_new_tokens=64)
MESH = ServeSpec("stablelm-1.6b", n_max=2048, max_batch=2,
                 prompt_lens=(768, 1024, 1280, 1536), max_new_tokens=32)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def check(cond, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileClock:
    """Seconds of backend compilation (persistent-cache hits excluded)."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def device_check(min_count: int):
    if "REPRO_PALLAS_INTERPRET" in os.environ:
        fail("REPRO_PALLAS_INTERPRET is set: it would run the jnp twins "
             "instead of the Pallas kernels")
    devs = jax.devices()
    d = devs[0]
    print(f"[a] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    check(d.platform == "tpu", f"first device is {d.platform}, not a TPU")
    check(len(devs) >= min_count, f"needs {min_count} devices, "
          f"found {len(devs)}")
    return d, len(devs)


def init_params(cfg):
    return jax.jit(lambda k: M.init_params(cfg, k))(jax.random.PRNGKey(0))


def make_requests(cfg, spec: ServeSpec):
    stream = SyntheticLMStream(cfg.vocab_size, seed=1)
    return [Request(uid=i, prompt=stream.sequence(n),
                    max_new_tokens=spec.max_new_tokens)
            for i, n in enumerate(spec.prompt_lens)]


def make_engine(cfg, params, spec: ServeSpec, **kw):
    return build_engine(cfg, params, engine="paged", n_max=spec.n_max,
                        max_batch=spec.max_batch, chunk_size=spec.chunk_size,
                        block_size=spec.block_size,
                        prefill_budget=spec.prefill_budget, **kw)


def serve(engine, reqs, on_step=None):
    """Run ``reqs`` to completion; enforce the failure rules; return
    {uid: output tokens} and the wall seconds."""
    for r in reqs:
        engine.submit(r)
    if on_step is not None:
        step = engine.step_serve

        def step_and_observe():
            step()
            on_step(engine)
        engine.step_serve = step_and_observe
    t0 = time.perf_counter()
    try:
        done = engine.run()
    finally:
        engine.__dict__.pop("step_serve", None)   # no engine ↔ hook cycle
    wall = time.perf_counter() - t0
    check(len(done) == len(reqs), f"{len(done)} of {len(reqs)} finished")
    quarantined = {r.uid for r in engine.quarantined}
    for r in done:
        check(not r.failed, f"request {r.uid} failed: {r.error}")
        check(r.uid not in quarantined, f"request {r.uid} quarantined")
        check(r.degraded_steps == 0,
              f"request {r.uid}: {r.degraded_steps} degraded steps")
        check(r.fetch_timeouts == 0,
              f"request {r.uid}: {r.fetch_timeouts} fetch timeouts")
        check(len(r.output) == r.max_new_tokens,
              f"request {r.uid}: {len(r.output)} of {r.max_new_tokens} "
              f"tokens")
    return {r.uid: np.asarray(r.output) for r in done}, wall


def all_decoding(reqs) -> bool:
    """Every request has emitted its first token and none has finished."""
    return all(r.token_times and r.output is None for r in reqs)


def layer0_snapshot(engine) -> dict:
    """Copies of the first ParisKV layer's pool, bucket histogram and the
    block tables/region ends, taken between decode chunks (the next chunk
    donates the live state)."""
    state, bt = engine.chunk_args()[1:3]
    lc = next(lc for stage in state.caches for lc in stage.values()
              if "hist" in lc)
    return dict(pool=jax.tree.map(lambda a: a[0], lc["kv"]),
                hist=lc["hist"][0], bt=np.asarray(bt),
                enc_end=np.asarray(state.regions.enc_end))


def phase_resident(cfg, params, spec: ServeSpec, clock: CompileClock):
    reqs = make_requests(cfg, spec)
    snap = {}

    def observe(engine):
        if not snap and all_decoding(reqs):
            snap.update(layer0_snapshot(engine))

    c0 = clock.total
    with make_engine(cfg, params, spec) as engine:
        engine.start()
        text = engine.lower_chunk().compile().as_text()
        n_kernels = text.count("tpu_custom_call")
        print(f"[b] resident: compiled decode chunk contains "
              f"tpu_custom_call x{n_kernels}", flush=True)
        check(n_kernels > 0, "the compiled decode chunk runs no Pallas "
              "kernel (no tpu_custom_call)")
        out, wall = serve(engine, reqs, on_step=observe)
    check(snap, "never saw all requests decoding at once")
    tokens = sum(len(t) for t in out.values())
    print(f"[b] resident: compile {clock.total - c0:.3f}s wall {wall:.3f}s "
          f"tokens {tokens}; no request failed, was quarantined or "
          f"degraded", flush=True)
    return out, snap


def phase_offload(cfg, params, spec: ServeSpec, clock: CompileClock,
                  resident: dict):
    reqs = make_requests(cfg, spec)
    num_blocks = spec.max_batch * spec.n_max // spec.block_size
    c0 = clock.total
    with make_engine(cfg, params, spec, offload=True,
                     num_device_blocks=num_blocks // 4) as engine:
        out, wall = serve(engine, reqs)
    hits = sum(r.staging_hits for r in reqs)
    misses = sum(r.staging_misses for r in reqs)
    fetched = sum(r.fetched_bytes for r in reqs)
    tokens = sum(len(t) for t in out.values())
    print(f"[c] offload: compile {clock.total - c0:.3f}s wall {wall:.3f}s "
          f"tokens {tokens} staging_hit_rate "
          f"{hits / max(hits + misses, 1):.6f} fetched_bytes {fetched}",
          flush=True)
    for uid, toks in resident.items():
        diff = np.flatnonzero(out[uid] != toks)
        check(diff.size == 0, f"request {uid}: offloaded tokens diverge "
              f"from resident at step {diff[:1].tolist()}")
    print("[c] offload: greedy tokens equal the resident engine's",
          flush=True)


def phase_kernels(cfg, snap: dict, spec: ServeSpec, clock: CompileClock):
    pcfg = cfg.pariskv
    pool, bt, hist = snap["pool"], jnp.asarray(snap["bt"]), snap["hist"]
    enc = jnp.asarray(snap["enc_end"])
    b, bs = bt.shape[0], spec.block_size
    G = cfg.num_kv_heads
    q = jax.random.normal(jax.random.PRNGKey(3),
                          (b, G, cfg.num_heads // G, cfg.head_dim))
    qt = encode_query(q, pcfg, SV.rotation_signs(cfg))
    C = pcfg.candidate_count(spec.n_max)
    c0 = clock.total

    def retrieve(use_kernels):
        return jax.jit(lambda *a: retrieve_paged_fused(
            *a, pcfg, C, pcfg.top_k, use_kernels=use_kernels))(
                pool, bt, qt, hist, enc)

    kern, twin = retrieve(True), retrieve(False)
    coarse_k, coarse_t = np.asarray(kern.coarse_scores), \
        np.asarray(twin.coarse_scores)
    check(np.array_equal(coarse_k, coarse_t),
          f"Stage-I scores differ at {int((coarse_k != coarse_t).sum())} "
          f"positions")
    cand = np.asarray(twin.cand_indices)
    check(np.array_equal(np.asarray(kern.cand_indices), cand),
          "Stage-I candidate sets differ")
    print(f"[d] kernels: Stage-I scores equal exactly over "
          f"{int((coarse_t >= 0).sum())} scored keys; candidate sets equal "
          f"({cand.shape[-1]} per head)", flush=True)

    # Stage II on the shared candidates: kernel vs twin estimates
    blk = np.take_along_axis(np.asarray(bt), (cand // bs).reshape(b, -1),
                             axis=1).reshape(cand.shape)
    phys = jnp.asarray(np.maximum(blk, 0) * bs + cand % bs)
    est_k = np.asarray(jax.jit(lambda *a: rerank_paged_kernel(
        *a, m=pcfg.m, bits=pcfg.magnitude_bits))(
            pool.meta_codes, pool.meta_w, phys, qt.q_sub, qt.q_norm))
    est_t = np.asarray(jax.jit(lambda *a: R.rerank_paged(*a, pcfg))(
        pool.meta_codes, pool.meta_w, phys, jnp.asarray(cand), qt, enc))
    valid = est_t > R.NEG_INF / 2
    scale = np.abs(est_t[valid]).max()
    err = np.abs(est_k[valid] - est_t[valid]).max() / scale
    print(f"[d] kernels: Stage-II max |kernel - twin| / max|est| = {err:.3e} "
          f"over {int(valid.sum())} candidates (limit {EST_RTOL})",
          flush=True)
    check(err <= EST_RTOL, f"Stage-II estimates differ by {err:.3e}")

    # winners: equal sets, apart from ties at the k-th twin estimate
    win_k = np.asarray(kern.indices).reshape(-1, pcfg.top_k)
    win_t = np.asarray(twin.indices).reshape(-1, pcfg.top_k)
    kth = np.asarray(twin.scores).reshape(-1, pcfg.top_k).min(-1)
    cand_rows = cand.reshape(-1, cand.shape[-1])
    est_rows = est_t.reshape(-1, cand.shape[-1])
    tied_rows = 0
    for i in range(win_t.shape[0]):
        diff = set(win_k[i].tolist()) ^ set(win_t[i].tolist())
        if not diff:
            continue
        tied_rows += 1
        for x in diff:
            e = est_rows[i][cand_rows[i] == x][0]
            check(abs(e - kth[i]) <= EST_RTOL * scale,
                  f"head-row {i}: winner {x} (estimate {e}) differs "
                  f"beyond a tie with the k-th estimate {kth[i]}")
    print(f"[d] kernels: winners equal in {win_t.shape[0] - tied_rows} of "
          f"{win_t.shape[0]} head-rows, the rest differ only by ties at "
          f"the k-th estimate; compile {clock.total - c0:.3f}s", flush=True)


def pool_kv_leaves(state):
    return [leaf for stage in state.caches for lc in stage.values()
            if "hist" in lc for leaf in (lc["kv"].k, lc["kv"].v)]


def phase_mesh(n: int, spec: ServeSpec, clock: CompileClock):
    cfg = configs.get(spec.arch)
    params = init_params(cfg)
    out = {}
    for shards in (1, n):
        c0 = clock.total
        reqs = make_requests(cfg, spec)
        with make_engine(cfg, params, spec, mesh_shards=shards) as engine:
            out[shards], wall = serve(engine, reqs)
            if shards > 1:
                per_dev = {}
                for leaf in pool_kv_leaves(engine.chunk_args()[1]):
                    shards_ = leaf.addressable_shards
                    check(len({s.device for s in shards_}) == n,
                          f"a pool K/V leaf spans {len(shards_)} devices")
                    for s in shards_:
                        check(s.data.nbytes * n == leaf.nbytes,
                              f"device {s.device} holds {s.data.nbytes} of "
                              f"{leaf.nbytes} bytes of a pool K/V leaf")
                        per_dev[s.device.id] = (per_dev.get(s.device.id, 0)
                                                + s.data.nbytes)
                total = sum(per_dev.values())
                print(f"[mesh] pool K/V bytes per device: "
                      f"{dict(sorted(per_dev.items()))} of {total} "
                      f"(1/{n} each)", flush=True)
        tokens = sum(len(t) for t in out[shards].values())
        print(f"[mesh] mesh_shards={shards}: compile "
              f"{clock.total - c0:.3f}s wall {wall:.3f}s tokens {tokens}",
              flush=True)
    for uid, toks in out[1].items():
        diff = np.flatnonzero(out[n][uid] != toks)
        check(diff.size == 0, f"request {uid}: mesh_shards={n} tokens "
              f"diverge from one device at step {diff[:1].tolist()}")
    print(f"[mesh] greedy tokens identical at mesh_shards=1 and {n}",
          flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run only the N-chip mesh phase")
    args = ap.parse_args(argv)
    enable_compile_cache()
    dev, count = device_check(max(args.mesh, 1))
    clock = CompileClock()
    if args.mesh:
        phase_mesh(args.mesh, MESH, clock)
    else:
        cfg = configs.get(ONE_CHIP.arch)
        params = init_params(cfg)
        resident, snap = phase_resident(cfg, params, ONE_CHIP, clock)
        phase_offload(cfg, params, ONE_CHIP, clock, resident)
        phase_kernels(cfg, snap, ONE_CHIP, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
