"""The decode step's phase scopes (core.phases) in the compiled decode
chunk of every engine path, the engine's host spans in a profiler trace,
and the requests' submit/admit stamps."""
import glob

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import phases
from repro.models import model as M
from repro.serving import PagedServingEngine, Request, ServingEngine
from repro.serving import engine as E

PATHS = {
    "contiguous": dict(paged=False),
    "fused": dict(),
    "fused_fill": dict(prefill_budget=8),
    "meta_view": dict(fused=False),
    "tiered": dict(offload=True, num_device_blocks=12),
}


@pytest.fixture(scope="module")
def model():
    cfg = configs.smoke("qwen2-1.5b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _engine(cfg, params, paged=True, **kw):
    if not paged:
        return ServingEngine(cfg, params, n_max=256, max_batch=2,
                             chunk_size=4, **kw)
    return PagedServingEngine(cfg, params, n_max=256, max_batch=2,
                              block_size=32, chunk_size=4, **kw)


def chunk_op_names(eng) -> list:
    """``op_name`` of every instruction of the compiled decode chunk."""
    eng.start()
    text = eng.lower_chunk().compile().as_text()
    eng.close()
    return [line.split('op_name="', 1)[1].split('"', 1)[0]
            for line in text.splitlines() if 'op_name="' in line]


def check_scopes(names, fill: bool) -> None:
    on_path = [[p for p in n.split("/") if p in phases.ALL] for n in names]
    seen = {s for path in on_path for s in path}
    want = set(phases.DECODE) | ({phases.FILL} if fill else set())
    assert want <= seen, sorted(want - seen)
    # an op belongs to its outermost name: the decode phases never nest
    # in one another (the fill runs whatever it needs under its own)
    nested = {"/".join(p) for p in on_path
              if len(p) > 1 and p[0] != phases.FILL}
    assert not nested, sorted(nested)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_decode_phase_is_named_once(model, path):
    cfg, params = model
    kw = PATHS[path]
    names = chunk_op_names(_engine(cfg, params, **kw))
    check_scopes(names, fill="prefill_budget" in kw)


def test_engine_spans_reach_the_profiler_trace(model, tmp_path):
    from jax.profiler import ProfileData
    cfg, params = model
    eng = _engine(cfg, params, prefill_budget=8)
    eng.start()
    eng.submit(Request(uid=5, prompt=np.arange(20, dtype=np.int32),
                       max_new_tokens=3))
    eng.step_serve()                     # compile outside the trace
    eng.submit(Request(uid=6, prompt=np.arange(12, dtype=np.int32),
                       max_new_tokens=3))
    jax.profiler.start_trace(str(tmp_path))
    while eng.pending():
        eng.step_serve()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = {ev.name for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for ev in ln.events}
    want = {E.SPAN_ADMIT, E.SPAN_PRE_CHUNK, E.SPAN_DISPATCH, E.SPAN_WAIT,
            E.SPAN_COLLECT}
    assert want <= names, sorted(want - names)


@pytest.mark.parametrize("paged,budget", [(False, 0), (True, 8)],
                         ids=["resident_solo", "paged_chunked"])
def test_submit_and_admit_stamps(model, paged, budget):
    cfg, params = model
    eng = _engine(cfg, params, paged=paged, prefill_budget=budget)
    eng.start()
    rng = np.random.RandomState(1)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab_size, (n,))
                    .astype(np.int32), max_new_tokens=4)
            for i, n in enumerate([30, 17, 41])]
    assert all(r.t_submit is None and r.t_admit is None for r in reqs)
    for r in reqs:
        eng.submit(r)
        assert r.t_submit is not None and r.t_admit is None
    eng.step_serve()
    # two slots: the third request is still queued
    assert reqs[2].t_admit is None
    while eng.pending():
        eng.step_serve()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.token_times[0]
        assert r.ttft_s == pytest.approx(r.token_times[0] - r.t_admit)
    assert reqs[0].t_admit <= reqs[1].t_admit <= reqs[2].t_admit
