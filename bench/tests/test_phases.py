"""The phase split of a traced window (``bench/phases.py``) on a small
compiled-chunk text and a small recorded trace, the per-token reading,
and the compiled chunk rebuilt from shapes against the one that ran."""
from pathlib import Path

import pytest

from bench import phases, trace_reduce
from bench.harness import BenchError
from conftest import tiny_cell
from test_counts import traced_run

HLO = (Path(__file__).parent / "data" / "chunk_small.hlo.txt").read_text()
SPANS = ["bench.step_serve", "bench.submit", "bench.wait_arrival"]


def test_scope_map_reads_every_instruction_with_an_op_name():
    scopes = phases.scope_map(HLO)
    assert scopes["fusion.4"].endswith("pariskv.tier_table/reduce_max")
    assert scopes["_collision_paged_pallas.1"].endswith(
        "pariskv.stage1/pjit(_collision_paged_pallas)/pallas_call")
    assert scopes["while.7"] == "jit(<lambda>)/while"
    assert "copy.10" not in scopes          # no metadata, no scope
    assert "gte.9" in scopes                # a ROOT instruction


def test_an_op_the_compiler_left_unnamed_takes_its_makers_name():
    """A rewritten scatter and its fusion carry no metadata: the fusion
    takes its producers' name (through unnamed ones), or its fused root's;
    an unnamed copy stays unnamed."""
    scopes = phases.scope_map(HLO)
    assert phases.outermost(scopes["fusion.22"]) == "pariskv.select"
    assert phases.outermost(scopes["reshape.21"]) == "pariskv.select"
    assert phases.outermost(scopes["fusion.24"]) == "pariskv.tier_table"
    assert "copy.23" not in scopes


def test_outermost_name_charges_the_op():
    # a select op inside the chunked fill counts as fill
    assert phases.outermost(phases.scope_map(HLO)["fusion.5"]) == \
        "model.fill"
    assert phases.outermost("jit(f)/while/body/pariskv.promote/cond/"
                            "branch_1_fun/pariskv.tier_table/x") == \
        "pariskv.promote"
    assert phases.outermost("jit(f)/while/body/closed_call") is None


def test_split_charges_each_op_once_and_unscoped_takes_the_rest():
    scopes = phases.scope_map(HLO)
    op_s = {"fusion.4": 1.0, "fusion.5": 2.0, "_collision_paged_pallas.1":
            0.5, "fusion.6": 4.0, "_rerank_pallas.2": 0.25, "fusion.7": 0.125,
            "fusion.8": 8.0, "conditional.9": 0.0625, "copy.10": 16.0,
            "while.7": 32.0, "fusion.99": 64.0}   # fusion.99: not the chunk's
    sec = phases.split(op_s, scopes)
    assert sec == {"pariskv.tier_table": 1.0, "pariskv.stage1": 0.5,
                   "pariskv.select": 4.0, "pariskv.stage2": 0.25,
                   "pariskv.attend": 0.125, "pariskv.promote": 0.0625,
                   "model.dense": 8.0, "model.fill": 2.0,
                   "unscoped": 16.0 + 32.0 + 64.0}
    assert sum(sec.values()) == sum(op_s.values())


def test_own_time_inside_a_while():
    """The loop's own time (its span less the ops in it) is unscoped; the
    body's ops keep theirs."""
    ops = [("%while.7 = (s32[]) while(%t), condition=%c, body=%b", 0, 10000),
           ("%fusion.4 = f32[8]{0} fusion(%x), kind=kLoop", 1000, 2000),
           ("%_rerank_pallas.2 = f32[12,1,512]{2,1,0} custom-call(%y)",
            4000, 5000),
           ("%fusion.8 = bf16[8,1536]{1,0} fusion(%z), kind=kOutput",
            12000, 1000)]
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [list(e) for e in ops]}]}]
    red = trace_reduce.reduce(planes, SPANS)
    sec = phases.split(red["op_s"], phases.scope_map(HLO))
    assert sec["unscoped"] == pytest.approx(3e-6, abs=1e-15)
    assert sec["pariskv.tier_table"] == pytest.approx(2e-6, abs=1e-15)
    assert sec["pariskv.stage2"] == pytest.approx(5e-6, abs=1e-15)
    assert sec["model.dense"] == pytest.approx(1e-6, abs=1e-15)
    assert sum(sec.values()) == pytest.approx(sum(red["op_s"].values()),
                                              abs=1e-15)


def _run(monkeypatch, op_s, hlo=HLO):
    monkeypatch.setattr(phases, "chunk_text", lambda run: hlo)
    return traced_run({"busy_s": 0.5, "op_s": op_s})


def test_ms_per_token(monkeypatch):
    op_s = {"fusion.4": 0.010, "_collision_paged_pallas.1": 0.002,
            "fusion.6": 0.004, "_rerank_pallas.2": 0.001, "fusion.7": 0.003,
            "fusion.8": 0.006, "while.7": 0.0005}
    run = _run(monkeypatch, op_s)
    # two tokens decoded in the traced window (test_counts.traced_run)
    assert phases.ms_per_token(run, "pariskv.tier_table") == \
        pytest.approx(1e3 * 0.010 / 2)
    assert phases.ms_per_token(run, "model.dense") == \
        pytest.approx(1e3 * 0.006 / 2)
    assert phases.ms_per_token(run, "unscoped") == pytest.approx(0.25)
    # optional phases read 0 when they did not run
    assert phases.ms_per_token(run, "model.fill") == 0.0
    assert phases.ms_per_token(run, "pariskv.promote") == 0.0


def test_a_required_phase_missing_is_an_error(monkeypatch):
    run = _run(monkeypatch, {"fusion.4": 0.010, "fusion.8": 0.006})
    with pytest.raises(BenchError, match="pariskv.select"):
        phases.ms_per_token(run, "pariskv.select")


def test_a_program_that_names_no_phase_reads_nothing(monkeypatch):
    bare = HLO.replace("pariskv.", "x").replace("model.", "y")
    run = _run(monkeypatch, {"fusion.4": 0.010}, hlo=bare)
    assert phases.ms_per_token(run, "pariskv.select") is None
    assert phases.ms_per_token(run, "unscoped") is None


def test_no_device_op_or_no_token_reads_nothing(monkeypatch):
    monkeypatch.setattr(phases, "chunk_text", lambda run: HLO)
    assert phases.ms_per_token(traced_run({"busy_s": 0.0, "op_s": {}}),
                               "model.dense") is None
    run = traced_run({"busy_s": 0.5, "op_s": {"fusion.8": 1.0}})
    run.recs[0].token_times = lambda: [0.5]      # nothing in the window
    assert phases.ms_per_token(run, "model.dense") is None


def test_idle_gaps_take_the_engine_spans():
    """Inside the harness's span the engine's own spans are innermost, so
    an idle gap names what the engine was doing."""
    from repro.serving import engine as E
    host = [("bench.step_serve", 0, 10000), (E.SPAN_ADMIT, 100, 900),
            (E.SPAN_DISPATCH, 1000, 500), (E.SPAN_WAIT, 1500, 6000),
            (E.SPAN_COLLECT, 7500, 2000)]
    dev = [("%fusion.4 = f32[8]{0} fusion(%x)", 0, 200),
           ("%fusion.8 = f32[8]{0} fusion(%x)", 500, 2500),
           ("%fusion.4 = f32[8]{0} fusion(%x)", 3500, 4000),
           ("%fusion.8 = f32[8]{0} fusion(%x)", 9500, 500)]
    planes = [{"name": "/host:CPU", "lines": [
                  {"name": "python", "events": [list(e) for e in host]}]},
              {"name": "/device:TPU:0", "lines": [
                  {"name": "XLA Ops", "events": [list(e) for e in dev]}]}]
    names = SPANS + [E.SPAN_ADMIT, E.SPAN_PRE_CHUNK, E.SPAN_DISPATCH,
                     E.SPAN_WAIT, E.SPAN_COLLECT]
    gaps = dict((round(g * 1e9), n)
                for n, g in trace_reduce.reduce(planes, names)["gaps"])
    assert gaps == {300: E.SPAN_ADMIT, 500: E.SPAN_WAIT,
                    2000: E.SPAN_COLLECT}


def test_chunk_rebuilt_from_shapes_is_the_one_that_ran(root):
    """``chunk_text`` compiles the cell's chunk from shapes alone; its
    instruction names and scopes are those of the program the engine
    served with."""
    import numpy as np
    from types import SimpleNamespace
    from bench import harness, weights
    from repro.serving import Request
    root.add_cell("tiny", "tiny-bf16", "tiny-open", tiny_cell(3, 0.2))
    cell = harness.load_cell(root.path, "tiny")
    arch = harness.load_arch(root.path, cell.config)
    mc = arch.program_config(cell.config)
    engine = harness._engine(cell, mc, weights.make(arch.layout(mc), mc, 7))
    engine.start()
    harness._warm_up(engine, cell, mc.vocab_size)
    rng = np.random.default_rng(0)
    for uid, n in enumerate([50, 90, 20]):
        engine.submit(Request(uid=uid, prompt=rng.integers(0, 512, n)
                              .astype(np.int32), max_new_tokens=10))
    while engine.pending():
        engine.step_serve()
    served = engine.lower_chunk().compile().as_text()
    rebuilt = phases.chunk_text(SimpleNamespace(arch=arch, mc=mc,
                                                cell=cell))
    assert phases.scope_map(rebuilt) == phases.scope_map(served)
    assert {phases.outermost(n) for n in phases.scope_map(served).values()} \
        >= set(phases.VOCAB)
