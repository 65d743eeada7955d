"""One run of one cell: load the cell's files, build the engine, warm up,
drive the window, reduce what was recorded to metrics, check the served
tokens against the plain reference, print the result line.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json`` (its ``arch`` names
``bench/archs/<arch>.py``, ``dense`` where it names none: the model's
program config, weight layout, reference forward and work counts),
``bench/traffic/<traffic>.json`` (its ``generator`` names
``bench/generators/<generator>.py``), ``bench/workloads/<cell>.json`` and
``bench/metrics/<metric>.py`` (or the reader of the metric's stem, see
``metric_file``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        super().__init__(f"bench: {msg}")


# ------------------------------------------------------------- the spec ----
def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    entry: dict           # the BENCHMARK.json workload entry
    cell: dict            # bench/workloads/<name>.json
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<traffic>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    run_seconds: int


def load_cell(root: Path, name: str) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    d = root / "bench"
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(root, name, entry, _json(d / "workloads" / f"{name}.json"),
                _json(d / "configs" / f"{entry['config']}.json"),
                _json(d / "traffic" / f"{entry['traffic']}.json"),
                e2e, per_layer, bench["run_seconds"])


def load_arch(root: Path, conf: dict):
    """The module of the configuration's architecture,
    ``bench/archs/<arch>.py`` (``dense`` where the file names none)."""
    rel = f"bench/archs/{conf.get('arch', 'dense')}.py"
    if not (root / rel).is_file():
        raise BenchError(f"{conf['name']}: no {rel} for its arch")
    return load_module(root / rel)


# ------------------------------------------------------------- recording ----
@dataclasses.dataclass
class Rec:
    """One request's life as the harness saw it (host clock)."""
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float                     # absolute due time
    req: object = None             # the engine's Request
    submitted: float = math.nan
    left_queue: float = math.nan

    def tokens(self) -> np.ndarray:
        toks = self.req.output if self.req.output is not None \
            else self.req._tokens
        return np.asarray(toks if toks is not None else [], np.int32)

    def token_times(self) -> List[float]:
        return list(self.req.token_times or [])


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    cell: Cell
    arch: object                   # the module bench/archs/<arch>.py
    mc: object                     # program ModelConfig
    peaks: dict
    device_kind: str
    seconds: float
    t_process: float
    t0: float = 0.0                # window start
    t_end: float = 0.0             # end of the last chunk of the window
    recs: List[Rec] = dataclasses.field(default_factory=list)
    steps: List[tuple] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None   # trace_reduce.reduce() output
    tt0: float = 0.0               # traced window (host clock)
    tt1: float = 0.0
    filled: Dict[str, Dict[int, int]] = dataclasses.field(
        default_factory=dict)      # "tt0"/"tt1" -> uid -> prompt tokens in
    compiles_in_window: int = 0
    lateness: List[float] = dataclasses.field(default_factory=list)
    queue: List[int] = dataclasses.field(default_factory=list)  # per step


class CompileCounter:
    """Counts traces and backend compiles (persistent-cache hits are
    traced but not compiled)."""

    def __init__(self):
        import jax
        self.traces = 0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


# host spans the harness records around its calls into the engine; the
# trace reduction labels the device's idle gaps with them
SPANS = ("bench.step_serve", "bench.submit", "bench.wait_arrival")


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------- the driver ----
def _device(cell: Cell, require_tpu: bool, peaks: dict):
    import jax
    if require_tpu and "REPRO_PALLAS_INTERPRET" in os.environ:
        raise BenchError("REPRO_PALLAS_INTERPRET is set: the Pallas kernels "
                         "would not run")
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"no accelerator: {e}")
    d = devs[0]
    chips = cell.entry["chips"]
    if require_tpu:
        if d.platform != "tpu":
            raise BenchError(f"no TPU: the first device is {d.platform}")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, found "
                             f"{len(devs)}")
    if d.device_kind not in peaks["devices"]:
        raise BenchError(f"device kind {d.device_kind!r} is not in "
                         f"bench/peaks.json")
    return d, devs[:chips]


def _engine(cell: Cell, mc, params):
    from repro.serving import PagedServingEngine
    e = cell.cell["engine"]
    return PagedServingEngine(
        mc, params, n_max=e["n_max"], max_batch=e["max_batch"],
        block_size=e["block_size"], chunk_size=e["chunk_size"],
        prefill_budget=e["prefill_budget"], num_blocks=e.get("num_blocks"))


def _warm_up(engine, cell: Cell, vocab: int) -> None:
    """Compile the cell's programs before the window: a short request that
    fills over two mixed steps, decodes over two chunks and is evicted."""
    from repro.serving import Request
    e = cell.cell["engine"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, e["prefill_budget"] + 1).astype(np.int32)
    engine.submit(Request(uid=-1, prompt=prompt,
                          max_new_tokens=2 * e["chunk_size"] + 1))
    while engine.pending():
        engine.step_serve()


def _filled(engine, recs: List[Rec], t: float) -> Dict[int, int]:
    """Prompt tokens written so far, per request."""
    fill_pos = (np.asarray(engine._state.fill_pos)
                if engine._state.fill_pos is not None else None)
    slot_of = {r.uid: s for s, r in enumerate(engine._slots) if r is not None}
    out = {}
    for rec in recs:
        times = rec.token_times()
        if times and times[0] <= t:
            out[rec.uid] = len(rec.prompt)
        elif rec.uid in slot_of and fill_pos is not None:
            out[rec.uid] = int(fill_pos[slot_of[rec.uid]])
        else:
            out[rec.uid] = 0
    return out


def _window(engine, run: Run, plan, trace_dir: Optional[Path]) -> None:
    """Drive the engine for ``run.seconds`` from the first chunk boundary:
    submit what is due, step, and (traced runs) profile a sub-window."""
    import jax
    from repro.serving import Request
    cell = run.cell
    closed = cell.traffic["loop"] == "closed"
    conc = cell.traffic.get("concurrency", 1)
    pending = list(plan)
    tr = cell.cell.get("trace", {})
    tracing = traced = False
    run.t0 = time.perf_counter()
    trace_from = run.t0 + tr.get("after_s", 0.0)
    for rec in run.recs:           # closed-loop sessions admitted in set-up
        rec.due = run.t0 if math.isnan(rec.due) else rec.due
    while True:
        now = time.perf_counter()
        if now >= run.t0 + run.seconds:
            break
        if trace_dir is not None and not traced and not tracing \
                and now >= trace_from:
            run.filled["tt0"] = _filled(engine, run.recs, now)
            jax.profiler.start_trace(str(trace_dir))
            run.tt0 = time.perf_counter()
            tracing = True
        with _span("bench.submit"):
            if closed:
                busy = sum(1 for r in run.recs if r.req.output is None)
                while pending and busy < conc:
                    p = pending.pop(0)
                    rec = Rec(p.index, p.prompt, p.max_new_tokens, due=now)
                    run.recs.append(rec)
                    busy += 1
            else:
                while pending and run.t0 + pending[0].due_s <= now:
                    p = pending.pop(0)
                    rec = Rec(p.index, p.prompt, p.max_new_tokens,
                              due=run.t0 + p.due_s)
                    run.recs.append(rec)
            for rec in run.recs:
                if rec.req is None:
                    rec.req = Request(uid=rec.uid, prompt=rec.prompt,
                                      max_new_tokens=rec.max_new_tokens)
                    rec.submitted = time.perf_counter()
                    run.lateness.append(rec.submitted - rec.due)
                    engine.submit(rec.req)
        if engine.pending():
            queued = {r.uid for r in engine.queue}
            t_step = time.perf_counter()
            with _span("bench.step_serve"):
                engine.step_serve()
            t_done = time.perf_counter()
            run.steps.append((t_step, t_done))
            run.queue.append(len(engine.queue))
            still = {r.uid for r in engine.queue}
            for rec in run.recs:
                if rec.uid in queued and rec.uid not in still:
                    rec.left_queue = t_step
        elif pending and not closed:
            with _span("bench.wait_arrival"):
                time.sleep(max(0.0, min(run.t0 + pending[0].due_s,
                                        run.t0 + run.seconds)
                               - time.perf_counter()))
        else:
            break
        if tracing and time.perf_counter() >= run.tt0 + tr["seconds"]:
            run.tt1 = time.perf_counter()
            jax.profiler.stop_trace()
            run.filled["tt1"] = _filled(engine, run.recs, run.tt1)
            tracing, traced = False, True
    run.t_end = time.perf_counter()
    if tracing:
        run.tt1 = run.t_end
        jax.profiler.stop_trace()
        run.filled["tt1"] = _filled(engine, run.recs, run.tt1)


def _setup_sessions(engine, run: Run, plan) -> list:
    """Closed loop: admit the first sessions and, where the traffic says
    so, fill their prompts before the window opens."""
    from repro.serving import Request
    t = run.cell.traffic
    if t["loop"] != "closed":
        return plan
    conc = t.get("concurrency", 1)
    for p in plan[:conc]:
        rec = Rec(p.index, p.prompt, p.max_new_tokens, due=math.nan)
        rec.req = Request(uid=p.index, prompt=p.prompt,
                          max_new_tokens=p.max_new_tokens)
        rec.submitted = time.perf_counter()
        engine.submit(rec.req)
        run.recs.append(rec)
    if t.get("fill_in_setup"):
        e = run.cell.cell["engine"]
        budget = sum(-(-len(r.prompt) // (e["prefill_budget"]
                                          * e["chunk_size"])) + 2
                     for r in run.recs)
        while any(not r.token_times() for r in run.recs):
            if budget == 0:
                raise BenchError("the prompts filled in set-up never "
                                 "produced a first token")
            engine.step_serve()
            budget -= 1
    return plan[conc:]


def metric_file(root: Path, name: str) -> Path:
    """``bench/metrics/<name>.py``; a name split by cell kind, such as
    ``queue_wait_ms_p50.openloop``, falls back to the reader of its stem
    (``queue_wait_ms_p50.py``) where it has no file of its own."""
    d = root / "bench" / "metrics"
    own = d / f"{name}.py"
    return own if own.is_file() else d / f"{name.split('.')[0]}.py"


def _metrics(run: Run, specs: List[dict]) -> dict:
    out = {}
    for m in specs:
        mod = load_module(metric_file(run.cell.root, m["name"]))
        val = mod.read(run)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def _free_device() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_process: float, require_tpu: bool = True,
             out=None, control: bool = False, check: bool = True,
             traffic: Optional[dict] = None) -> dict:
    """One run; returns the result dict (also printed as the last line).
    ``traffic`` replaces the cell's mix and ``check=False`` skips the
    comparison (``correct`` is then None): for ``bench/sweep.py`` only.
    ``control`` judges the float8 control in the program's place
    (``correct``) beside the program (``program_correct``), with both
    readings: for ``bench/calibrate.py`` only."""
    out = out or sys.stdout
    cell = load_cell(root, workload)
    arch = load_arch(root, cell.config)
    if traffic is not None:
        cell.traffic = traffic
    peaks = _json(root / "bench" / "peaks.json")
    src = root / "src"
    if not (src / "repro").is_dir():
        raise BenchError(f"the program is not here: no {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax
    from repro.launch.serve import enable_compile_cache
    from repro.models import model as M
    from bench import correctness, weights

    dev, devs = _device(cell, require_tpu, peaks)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mc = arch.program_config(cell.config)
    lay = arch.layout(mc)
    weights.check_layout(lay, mc, jax.eval_shape(
        lambda: M.init_params(mc, jax.random.PRNGKey(0))))
    e = cell.cell["engine"]
    print(f"[cell] {workload}: config {cell.config['name']} "
          f"({arch.describe(mc)}), engine {json.dumps(e)}, traffic "
          f"{cell.entry['traffic']}, seed {seed}, seconds {seconds}, "
          f"trace {int(trace)}", file=out, flush=True)

    params = weights.make(lay, mc, seed)
    engine = _engine(cell, mc, params)
    gen = load_module(root / "bench" / "generators"
                      / f"{cell.traffic['generator']}.py")
    plan = gen.generate(cell.traffic, vocab_size=mc.vocab_size, seed=seed,
                        seconds=seconds)
    run = Run(cell, arch, mc, peaks, dev.device_kind, seconds, t_process)
    engine.start()
    _warm_up(engine, cell, mc.vocab_size)
    plan = _setup_sessions(engine, run, plan)
    counter = CompileCounter()
    trace_dir = None
    if trace:
        trace_dir = root / "bench_out" / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
    c0 = (counter.traces, counter.compiles)
    _window(engine, run, plan, trace_dir)
    run.compiles_in_window = counter.compiles - c0[1]
    traces_in_window = counter.traces - c0[0]
    stats = dev.memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)

    due = run.recs
    admitted = [r for r in due if r.req is not None and (
        not math.isnan(r.left_queue) or r.req._tokens is not None)]
    finished = [r for r in due if r.req is not None
                and r.req.output is not None]
    failed = [r for r in due if r.req is not None and r.req.failed]
    late = np.asarray(run.lateness or [0.0]) * 1e3
    half = len(run.queue) // 2
    q = [float(np.mean(part)) if part else 0.0
         for part in (run.queue[:half], run.queue[half:])]
    print(f"[window] {run.t_end - run.t0:.6f}s: requests due {len(due)}, "
          f"admitted {len(admitted)}, finished {len(finished)}, failed "
          f"{len(failed)}; queue mean first/second half {q[0]:.2f}/"
          f"{q[1]:.2f}; generator lateness ms p50 "
          f"{np.percentile(late, 50):.3f} max {late.max():.3f}; compiles "
          f"in window {run.compiles_in_window} (traces "
          f"{traces_in_window}); peak_bytes_in_use {peak} "
          f"(bytes_limit {stats.get('bytes_limit')})", file=out, flush=True)

    result = {"correct": False, "attempted": len(due),
              "failed": len(failed)}
    if trace:
        from bench import trace_reduce
        run.trace = trace_reduce.reduce(trace_reduce.load(trace_dir), SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = _metrics(run, cell.per_layer)
    else:
        metrics = _metrics(run, cell.end_to_end)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.tt1 - run.tt0
        result["breakdown"] = run.trace["breakdown"]

    sample = correctness.sample(run, seed)
    engine.close()
    del engine, params
    _free_device()
    if not check:
        result.update(correct=None, metrics=metrics, device=device)
        print(json.dumps(result), file=out, flush=True)
        return result
    checks, ctrl, readings = correctness.check(run, sample, seed,
                                               control=control)
    for c in (checks, ctrl):
        if c is not None:
            c["failed_requests"] = {"value": len(failed), "limit": 0,
                                    "rule": "<="}
    result["correct"] = all(correctness.passed(c) for c in checks.values())
    if control:
        # the control in the program's place, judged by the same limits:
        # it has to come out not correct
        result["program_correct"] = result["correct"]
        result["correct"] = all(correctness.passed(c)
                                for c in ctrl.values())
        result["readings"] = readings
        checks = ctrl
    result.update(metrics=metrics, device=device)
    # the comparisons come last: on stderr, and as the line's last key
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['rule']} "
              f"{c['limit']!r})", file=sys.stderr)
    result["check"] = checks
    print(json.dumps(result), file=out, flush=True)
    return result
