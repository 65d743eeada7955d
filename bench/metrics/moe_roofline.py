"""The decode step's experts: least time of the routed and shared
experts' work for the tokens decoded in the traced window (weight bytes
and FLOPs, ``expert_work`` of the configuration's arch file) at the
chip's peaks, over the own device time of the ops under the program's
``moe.experts`` and ``moe.shared`` scopes inside ``model.dense`` (%)."""
from bench import counts, subphases


def read(run):
    secs = subphases.seconds(run, ("moe.experts", "moe.shared"))
    if not secs:
        return None
    tokens = counts.traced_work(run)["decode_tokens"]
    if tokens == 0:
        return None
    byts, flops = run.arch.expert_work(run.mc, tokens)
    t, bound = counts.roofline_time(byts, flops, counts.peak(run))
    print(f"[moe] {tokens} tokens, {secs:.6f}s on the device; {byts} "
          f"bytes, {flops} flops: {bound}-bound least time {t:.6e}s",
          flush=True)
    return 100.0 * t / secs
