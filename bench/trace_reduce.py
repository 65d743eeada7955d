"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

``load`` turns the trace into plain lists: planes with their lines and
events ``(name, start_ns, duration_ns)``. ``reduce`` works on that form
only, so a small recorded trace checks it (``bench/tests``):

* device busy time: the union of the op intervals on each TPU plane's
  op line, averaged over the planes;
* time per device op, by its HLO instruction name (an op event is named
  by the instruction's text, ``%fusion.3 = f32[...] fusion(...)``; the
  kernels' custom calls carry the name of the function jitted around
  their ``pallas_call``, ``%_rerank_pallas.1 = ...``), and the ten that
  took most, shown with the start of their text (the shapes say which
  work an op does). An op's time is its own: the ops nested in it on the same
  line (the body of a ``while`` or a ``conditional``) are taken out;
* idle gaps between device ops, each labelled by the harness span
  (``bench.*``, on the host plane) that covers its midpoint.
"""
from __future__ import annotations

import glob
from pathlib import Path
from typing import List, Sequence

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
NO_SPAN = "host outside bench spans"
TEXT = 160          # characters of an op's text shown in the breakdown


def load(trace_dir: Path) -> List[dict]:
    from jax.profiler import ProfileData
    files = glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not files:
        return []
    pd = ProfileData.from_file(files[0])
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def op_name(text: str) -> str:
    """The HLO instruction name of an op event: ``fusion.3`` for
    ``%fusion.3 = f32[8]{0} fusion(...)``."""
    return text.split(" ", 1)[0].lstrip("%")


def self_times(evs: Sequence[tuple]) -> List[tuple]:
    """(name, own seconds) of each event: its duration less that of the
    events nested in it (wholly inside it, on the same line)."""
    out = []
    stack: List[list] = []          # [end, index in out]
    for n, s, d in sorted(evs, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            out[stack[-1][1]][1] -= d
        out.append([op_name(n), d])
        stack.append([s + d, len(out) - 1])
    return [(n, d * 1e-9) for n, d in out]


def _union(iv: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def device_planes(planes: Sequence[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]


def op_events(plane: dict) -> List[tuple]:
    for ln in plane["lines"]:
        if ln["name"] == OPS_LINE:
            return ln["events"]
    return []


def reduce(planes: Sequence[dict], span_names: Sequence[str]) -> dict:
    devs = [p for p in device_planes(planes) if op_events(p)]
    if not devs:
        return {"busy_s": 0.0, "op_s": {}, "op_calls": {}, "gaps": [],
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy, op_s, op_calls, gaps, texts = 0.0, {}, {}, [], {}
    spans = []
    for p in planes:
        if p["name"] == HOST_PLANE:
            for ln in p["lines"]:
                spans += [(s, s + d, n) for n, s, d in ln["events"]
                          if n in span_names]
    spans.sort()
    for p in devs:
        evs = op_events(p)
        merged = _union([(s, s + d) for _, s, d in evs])
        busy += sum(e - s for s, e in merged) * 1e-9
        for n, t in self_times(evs):
            op_s[n] = op_s.get(n, 0.0) + t
            op_calls[n] = op_calls.get(n, 0) + 1
        for text, _, _ in evs:
            texts.setdefault(op_name(text), text.lstrip("%")[:TEXT])
        for (_, a), (b, _) in zip(merged, merged[1:]):
            mid = 0.5 * (a + b)
            label = NO_SPAN
            for s, e, n in spans:
                if s <= mid <= e:
                    label = n      # the innermost (latest-starting) wins
            gaps.append((label, (b - a) * 1e-9))
    n = len(devs)
    op_s = {k: v / n for k, v in op_s.items()}
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    gaps.sort(key=lambda g: -g[1])
    return {"busy_s": busy / n, "op_s": op_s, "op_calls": op_calls,
            "gaps": gaps,
            "breakdown": {"device_ops": [[texts[k], v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in gaps[:10]]}}


def kernel_time(red: dict, names: Sequence[str]) -> tuple:
    """(seconds, calls) of the device ops named ``<name>`` or
    ``<name>.<n>`` for any of ``names`` (HLO instruction names)."""
    s, c = 0.0, 0
    for op, t in red["op_s"].items():
        if any(op == k or op.startswith(k + ".") for k in names):
            s += t
            c += red["op_calls"][op]
    return s, c
