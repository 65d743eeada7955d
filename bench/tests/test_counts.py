import json
from types import SimpleNamespace

from bench import counts
from bench.harness import model_config
from conftest import REPO

QWEN = json.loads((REPO / "bench/configs/qwen2-1.5b.json").read_text())
PK = QWEN["pariskv"]


def qwen():
    import sys
    sys.path.insert(0, str(REPO / "src"))
    return model_config(QWEN)


def test_matrix_params_by_hand():
    # 28 x (1536·(12+2·2)·128 + 12·128·1536 + 3·1536·8960) + 1536·151936
    assert counts.matrix_params(qwen()) == 28 * 46_792_704 + 233_373_696


def test_prompt_flops_by_hand():
    mc = qwen()
    # 512 prompt tokens at positions 0..511 attend 1..512 keys
    attn = 4 * 12 * 128 * (512 * 513 // 2) * 28
    assert counts.prompt_flops(mc, 0, 512) == \
        2 * counts.matrix_params(mc) * 512 + attn
    # a later chunk: positions 512..1023 attend 513..1024 keys
    assert counts.prompt_flops(mc, 512, 1024) - \
        2 * counts.matrix_params(mc) * 512 == \
        4 * 12 * 128 * sum(range(513, 1025)) * 28


def test_decode_keys_and_regions():
    # after a 24576-token prompt the region ends at 24576 - 512 = 24064;
    # position 25000 attends 128 sink + 100 retrieved + the 768-wide
    # window above the region end
    assert counts.decode_keys(PK, 25000, 24064) == 128 + 100 + 768
    # a short prompt: region empty, window clipped at the sink
    assert counts.decode_keys(PK, 300, 128) == 128 + 0 + (301 - 128)
    toks = counts.decoded(PK, 24576, 0, 4)
    assert toks == [(24576, 24064), (24577, 24064), (24578, 24064)]
    # the region moves up 256 once p - e reaches 768
    toks = counts.decoded(PK, 24576, 1, 260)
    ends = dict(toks)
    assert ends[24832] == 24064 and ends[24833] == 24320


def test_stage_bytes_and_ops_by_hand():
    mc = qwen()
    n = 24064 - 128
    b1, o1 = counts.stage1(mc, PK, 24064)
    # ids: 2 kv heads x 16 subspaces bytes per key; table: 12 heads x 16
    # subspaces x 256 buckets x int32
    assert b1 == (n * 2 * 16 + 12 * 16 * 256 * 4) * 28
    assert o1 == n * 12 * 16 * 28
    b2, o2 = counts.stage2(mc, PK, 24064)
    C = 1915                       # ceil(0.08 x (24064 - 128))
    assert b2 == C * 12 * 16 * 8 * 28
    assert o2 == 2 * C * 12 * 128 * 28
    # a short prompt's region is empty: no Stage II work
    assert counts.stage2(mc, PK, 128) == (0, 0)


def test_roofline_time_picks_the_bound():
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    assert counts.roofline_time(819e9, 1.0, peak) == (1.0, "bytes")
    assert counts.roofline_time(1.0, 197e12, peak) == (1.0, "flops")


def traced_run(trace):
    rec = SimpleNamespace(req=object(), uid=0, prompt=[0] * 24576,
                          token_times=lambda: [0.5, 1.5, 1.5, 2.5])
    return SimpleNamespace(
        mc=qwen(), recs=[rec], tt0=1.0, tt1=2.0, trace=trace,
        cell=SimpleNamespace(config=QWEN, entry={"chips": 1},
                             cell={"engine": {"n_max": 32768}}),
        filled={"tt0": {0: 24576}, "tt1": {0: 24576}},
        peaks={"devices": {"k": {"bf16_flops": 1e12,
                                 "hbm_bytes_per_s": 1e11}}},
        device_kind="k")


def test_step_mfu_counts_the_traced_window():
    mc = qwen()
    run = traced_run({"busy_s": 0.5})
    # tokens 1 and 2 (decoded at 24576 and 24577) land in (1.0, 2.0]
    want = sum(2 * counts.matrix_params(mc)
               + 4 * 12 * 128 * counts.decode_keys(PK, p, 24064) * 28
               for p in (24576, 24577))
    assert abs(counts.step_mfu(run) - 100 * want / 1e12) < 1e-9
    assert counts.idle_share(run) == 50.0


def test_a_kernel_missing_from_a_device_trace_is_an_error():
    import pytest
    from bench.harness import BenchError
    trace = {"busy_s": 0.5, "op_s": {"fusion.1": 0.5,
                                     "_rerank_pallas.3": 0.01},
             "op_calls": {"fusion.1": 4, "_rerank_pallas.3": 2}}
    run = traced_run(trace)
    assert counts.kernel_roofline(run, "stage2", ("_rerank_pallas",)) > 0
    with pytest.raises(BenchError, match="no device op"):
        counts.kernel_roofline(run, "stage1", ("_collision_paged_pallas",))
    # a trace with no device op (the CPU's) reads nothing
    run.trace = {"busy_s": 0.0, "op_s": {}, "op_calls": {}}
    assert counts.kernel_roofline(run, "stage1", ("x",)) is None
