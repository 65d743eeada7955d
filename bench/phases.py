"""Device time of the decode step's phases in a traced window.

The program names each phase of a decode step with a ``jax.named_scope``
(``VOCAB``); every op of the compiled decode chunk carries the scope path
it was traced under in the ``op_name`` of its HLO metadata. The trace
reduction keeps each device op's own time by HLO instruction name
(``run.trace["op_s"]``), and the trace itself is gone when metrics are
read. So the instruction -> scope map is read from the compiled chunk:
after the window, in traced runs only, the cell's engine is built again
from the shapes of its parameters and state (no device memory) and its
chunk lowered, compiled and printed.

An op is charged to the **outermost** vocabulary name on its path (ops
of the chunked fill sit under ``model.fill`` whatever they call). An op
under no name, or not in the chunk (another program run in the window),
goes to ``unscoped``. A fusion carries the op name of its root: where it
mixes two phases it is charged to its root's. An op the compiler made
without a name (a rewritten scatter or cumulative sum) is charged by
what made its inputs (``scope_map``). Another program's op that shares
an instruction name with one of the chunk's (``fusion.3``) is charged
with it: the reduction has merged them by name already.

The names are the benchmark's own, not imported from the program: a
renamed scope stops the run (``ms_per_token``) rather than silencing
its metric. A program that names no phase at all reads nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

VOCAB = ("pariskv.tier_table", "pariskv.stage1", "pariskv.select",
         "pariskv.stage2", "pariskv.attend", "pariskv.promote",
         "model.dense", "model.fill")
# every decode step runs these: a trace with decode tokens and no time
# under one of them is an error
REQUIRED = ("pariskv.tier_table", "pariskv.stage1", "pariskv.select",
            "pariskv.stage2", "pariskv.attend", "model.dense")
UNSCOPED = "unscoped"

_INSTR = re.compile(r'^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*')
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_NAME = re.compile(r'%([\w.\-]+)')
_CALLS = re.compile(r'calls=%([\w.\-]+)')
DEPTH = 32     # producers walked back for an op the compiler left unnamed


def outermost(op_name: str) -> Optional[str]:
    """The first vocabulary name on an op's scope path, else None."""
    for part in op_name.split("/"):
        if part in VOCAB:
            return part
    return None


def _opcode_operands(rest: str) -> tuple:
    """(opcode, operand text) of an instruction's text after ``name = ``:
    skip the shape (its layout and a tuple's elements hold parentheses
    and spaces), then take the opcode and its parentheses."""
    depth, i = 0, 0
    for i, c in enumerate(rest):
        depth += (c in "([{") - (c in ")]}")
        if c == " " and depth == 0:
            break
    k = rest.find("(", i)
    if k < 0:
        return "", ""
    depth = 0
    for j in range(k, len(rest)):
        depth += (rest[j] == "(") - (rest[j] == ")")
        if depth == 0:
            return rest[i + 1:k], rest[k + 1:j]
    return rest[i + 1:k], ""


def scope_map(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> the ``op_name`` it is charged by.

    An instruction's own metadata names it. The compiler leaves some of
    the ops it makes unnamed (a scatter or a cumulative sum it rewrote,
    and the fusion around them): such an op takes the first vocabulary
    name among the instructions its fusion calls, root first, else among
    its producers, nearest first, through unnamed ones; else it stays
    unnamed. An unnamed copy stays unnamed: it moves a value, whoever
    made it."""
    own, args, calls, comps, copies = {}, {}, {}, {}, set()
    comp = None
    for line in hlo_text.splitlines():
        if line[:1] not in (" ", "") and line.rstrip().endswith("{"):
            comp = _NAME.search(line)
            comp = comp.group(1) if comp else None
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(2), line[m.end():]
        if comp is not None:
            # root first: the op a fusion is named after
            comps[comp].insert(0 if m.group(1) else len(comps[comp]), name)
        meta = _OP_NAME.search(rest)
        if meta:
            own[name] = meta.group(1)
        opcode, operands = _opcode_operands(rest)
        args[name] = _NAME.findall(operands)
        if opcode.startswith("copy"):
            copies.add(name)
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)

    def fused(name, depth=0):
        for inner in comps.get(calls.get(name), ()):
            if outermost(own.get(inner, "")):
                return own[inner]
            if depth < DEPTH and inner in calls:
                found = fused(inner, depth + 1)
                if found:
                    return found
        return None

    out = dict(own)
    for name in args:
        if name in own or name in copies:
            continue
        found = fused(name)
        seen, todo = {name}, list(args[name])
        while found is None and todo and len(seen) < DEPTH:
            nxt = todo.pop(0)
            if nxt in seen:
                continue
            seen.add(nxt)
            if outermost(own.get(nxt, "")):
                found = own[nxt]
            elif nxt not in own:
                found = fused(nxt)
                todo += args.get(nxt, [])
        if found:
            out[name] = found
    return out


def split(op_s: Dict[str, float], scopes: Dict[str, str]
          ) -> Dict[str, float]:
    """Own seconds per vocabulary name and ``unscoped``: every op of
    ``op_s`` in exactly one, so they add up to its total."""
    out = dict.fromkeys(VOCAB + (UNSCOPED,), 0.0)
    for op, t in op_s.items():
        out[outermost(scopes.get(op, "")) or UNSCOPED] += t
    return out


def chunk_text(run) -> str:
    """HLO text of the cell's decode chunk, compiled for the run's device
    from shapes alone."""
    import jax
    from bench import harness, weights
    params = jax.eval_shape(
        lambda: weights.make(run.arch.layout(run.mc), run.mc, 0))
    engine = harness._engine(run.cell, run.mc, params)
    # the state the chunk is compiled for, as shapes: no device memory
    engine._state = jax.eval_shape(engine._init_state)
    return engine.lower_chunk().compile().as_text()


def seconds(run) -> Optional[Dict[str, float]]:
    """``split`` of the traced window, or None where the trace holds no
    device op or the program names no phase. Computed once a run (every
    phase's reader asks), and kept on the run."""
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    if "phase_seconds" not in vars(run):
        scopes = scope_map(chunk_text(run))
        sec = None
        if any(outermost(n) for n in scopes.values()):
            sec = split(run.trace["op_s"], scopes)
            total = sum(run.trace["op_s"].values())
            print("[phases] own seconds " + ", ".join(
                f"{k} {v:.6f}" for k, v in sec.items())
                + f"; sum {sum(sec.values()):.6f} of {total:.6f}",
                flush=True)
        run.phase_seconds = sec
    return run.phase_seconds


def ms_per_token(run, phase: str) -> Optional[float]:
    """Own device ms of ``phase`` in the traced window per output token
    decoded in it; None where nothing was decoded or nothing can be read.
    A required phase with no time while tokens were decoded is an error."""
    from bench.counts import traced_work
    from bench.harness import BenchError
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    tokens = traced_work(run)["decode_tokens"]
    if tokens == 0:
        return None
    sec = seconds(run)
    if sec is None:
        return None
    if phase in REQUIRED and sec[phase] <= 0:
        raise BenchError(f"{phase}: {tokens} tokens decoded in the traced "
                         f"window, but no device op ran under that scope; "
                         f"own seconds by phase: {sec}")
    return 1e3 * sec[phase] / tokens
