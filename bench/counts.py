"""The benchmark's own counts of the algorithm's work, from the
configuration and the cell's shapes — never from how the program does it,
so a kernel that does the same work another way is held to the same
number.

* Model FLOPs per token: 2 × matrix parameters (LM head included), for
  prompt and output tokens alike, plus attention, 4 · heads · head_dim
  per attended key and layer: the causal prefix for a prompt token, the
  sink, the window and ``top_k`` retrieved keys for a decoded one.
* Stage I per decoded token: the centroid ids of every key of the
  retrieval region (kv heads × subspaces bytes each) plus the tier-weight
  table (int32 per query head, subspace and bucket); one lookup-add per
  key, query head and subspace.
* Stage II per decoded token: the 4-bit codes (uint32) and weights
  (float32) of the C candidates, per query head and subspace; one inner
  product over the padded head dim per candidate and query head. C is
  the algorithm's for the retrieval region's length, not the static
  size the program pads it to.
"""
from __future__ import annotations

from bench.reference import candidate_count, decode_region_ends


def matrix_params(mc) -> int:
    d, f, v = mc.d_model, mc.d_ff, mc.vocab_size
    H, G, hd = mc.num_heads, mc.num_kv_heads, mc.head_dim
    per_layer = d * (H + 2 * G) * hd + H * hd * d + 3 * d * f
    return mc.num_layers * per_layer + d * v


def _attn(mc, keys: int) -> int:
    return 4 * mc.num_heads * mc.head_dim * keys * mc.num_layers


def prompt_flops(mc, f0: int, f1: int) -> int:
    """Prompt positions f0..f1-1 (each attends to p + 1 keys)."""
    n = f1 - f0
    keys = (f0 + 1 + f1) * n // 2
    return 2 * matrix_params(mc) * n + _attn(mc, keys)


def decode_keys(pk: dict, p: int, enc: int) -> int:
    """Keys a decoded position ``p`` attends with region end ``enc``."""
    sink = pk["sink_size"]
    W = pk["local_size"] + pk["update_interval"]
    ws = max(p + 1 - W, 0)
    window = max(0, p + 1 - max(ws, enc, sink))
    return min(sink, p + 1) + min(pk["top_k"], max(enc - sink, 0)) + window


def decoded(pk: dict, prompt_len: int, first: int, last: int):
    """(position, region end) of output tokens first..last-1 (token 0 is
    the prompt's own; token j >= 1 was decoded at prompt_len + j - 1)."""
    lo = max(first, 1)
    if last <= lo:
        return []
    ends = decode_region_ends(pk, prompt_len, last - 1)
    return [(prompt_len + j - 1, int(ends[j - 1])) for j in range(lo, last)]


def decode_flops(mc, pk: dict, prompt_len: int, first: int, last: int) -> int:
    toks = decoded(pk, prompt_len, first, last)
    return (2 * matrix_params(mc) * len(toks)
            + sum(_attn(mc, decode_keys(pk, p, e)) for p, e in toks))


def stage1(mc, pk: dict, enc: int) -> tuple:
    """(bytes, ops) of Stage I for one decoded token, over all layers."""
    B = (1 << (mc.head_dim - 1).bit_length()) // pk["m"]
    n = max(enc - pk["sink_size"], 0)
    table = mc.num_heads * B * (1 << pk["m"]) * 4
    byts = n * mc.num_kv_heads * B + table
    return byts * mc.num_layers, n * mc.num_heads * B * mc.num_layers


def stage2(mc, pk: dict, enc: int) -> tuple:
    """(bytes, flops) of Stage II for one decoded token, over all layers:
    C candidates for a retrieval region of ``enc - sink`` keys."""
    dp = 1 << (mc.head_dim - 1).bit_length()
    B = dp // pk["m"]
    n = max(enc - pk["sink_size"], 0)
    C = candidate_count(pk, n) if n else 0
    byts = C * mc.num_heads * B * (4 + 4)
    return byts * mc.num_layers, 2 * C * mc.num_heads * dp * mc.num_layers


def roofline_time(byts: float, ops: float, peak: dict) -> tuple:
    """(seconds, bound) of the least time the chip could take."""
    tb = byts / peak["hbm_bytes_per_s"]
    tf = ops / peak["bf16_flops"]
    return (tb, "bytes") if tb >= tf else (tf, "flops")


# ---------------------------------------------- work of a traced window ----
def _decoded_in(rec, a: float, b: float) -> tuple:
    """Output-token indices (first, last) stamped in (a, b]."""
    idx = [j for j, t in enumerate(rec.token_times()) if a < t <= b]
    return (idx[0], idx[-1] + 1) if idx else (0, 0)


def traced_work(run) -> dict:
    """The algorithm's work done by the chunks of the traced window."""
    mc, pk = run.mc, run.cell.config["pariskv"]
    f0s, f1s = run.filled.get("tt0", {}), run.filled.get("tt1", {})
    flops = 0
    s1 = [0, 0]
    s2 = [0, 0]
    decode_tokens = 0
    for rec in run.recs:
        if rec.req is None:
            continue
        L = len(rec.prompt)
        flops += prompt_flops(mc, f0s.get(rec.uid, 0), f1s.get(rec.uid, 0))
        first, last = _decoded_in(rec, run.tt0, run.tt1)
        flops += decode_flops(mc, pk, L, first, last)
        for _, enc in decoded(pk, L, first, last):
            decode_tokens += 1
            b, o = stage1(mc, pk, enc)
            s1[0] += b
            s1[1] += o
            b, o = stage2(mc, pk, enc)
            s2[0] += b
            s2[1] += o
    return {"flops": flops, "stage1": tuple(s1), "stage2": tuple(s2),
            "decode_tokens": decode_tokens}


def peak(run) -> dict:
    return run.peaks["devices"][run.device_kind]


def step_mfu(run):
    """Model FLOPs of the traced window over its length at the bf16 peak,
    in %."""
    if run.trace is None or run.tt1 <= run.tt0:
        return None
    w = traced_work(run)
    if w["flops"] == 0:
        return None
    chips = run.cell.entry["chips"]
    return 100.0 * w["flops"] / ((run.tt1 - run.tt0) * chips
                                 * peak(run)["bf16_flops"])


def kernel_roofline(run, stage: str, names) -> float:
    """Least time of a stage's work over its kernels' device time, in %;
    None where the trace holds no device op or no work of the stage was
    counted. A device trace without the stage's kernel is an error: a
    renamed kernel must not silence its metric."""
    from bench.harness import BenchError
    from bench.trace_reduce import kernel_time
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    byts, ops = traced_work(run)[stage]
    if byts == 0:
        return None
    secs, calls = kernel_time(run.trace, names)
    if secs <= 0:
        calls = sorted(n for n in run.trace["op_s"] if "pallas" in n)
        raise BenchError(f"{stage}: {byts} bytes of work counted, but no "
                         f"device op of the trace is named {names}; its "
                         f"Pallas ops: {calls[:20]}")
    t, bound = roofline_time(byts, ops, peak(run))
    print(f"[{stage}] {calls} kernel calls, {secs:.6f}s on the device; "
          f"{byts} bytes, {ops} ops: {bound}-bound least time {t:.6e}s",
          flush=True)
    return 100.0 * t / secs


def idle_share(run):
    if run.trace is None or run.tt1 <= run.tt0 or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / (run.tt1 - run.tt0))
