"""The driving loop end to end on the CPU, at a tiny size."""
import hashlib
import os
import subprocess
import sys

import pytest

from conftest import REPO, run, tiny_cell, tiny_config


@pytest.mark.parametrize("traffic,batch", [("tiny-closed", 1),
                                           ("tiny-open", 3)])
def test_serves_a_tiny_cell(root, traffic, batch):
    root.add_cell("tiny", "tiny-bf16", traffic, tiny_cell(batch, 0.2))
    res = run(root, "tiny", seconds=2.0)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    want = {"decode_tok_s", "setup_s"}
    assert set(res["metrics"]) == want
    assert res["metrics"]["decode_tok_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert res["check"]["tokens_compared"]["value"] >= 8


def test_traced_run_reports_per_layer_metrics(root):
    root.add_cell("tiny", "tiny-bf16", "tiny-open", tiny_cell(3, 0.2))
    res = run(root, "tiny", seconds=2.0, trace=True)
    assert res["correct"]
    # no TPU plane in a CPU trace: the device readers read nothing
    assert "device_idle_share" not in res["metrics"]
    assert "queue_wait_ms_p50.openloop" in res["metrics"]
    assert "tpot_p90_ms" in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def _cli(cwd, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args],
                          cwd=cwd, env=e, capture_output=True, text=True,
                          timeout=300)


def test_cli_exits_nonzero_without_a_tpu():
    p = _cli(REPO, "--workload", "qwen2-longctx-decode", "--seed",
             str(2 ** 31 + 7), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_cli_exits_nonzero_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path, "--workload", "stablelm-code-openloop", "--seed",
             "3", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()


def _digest(path):
    return {str(p.relative_to(path)): hashlib.sha256(p.read_bytes()
                                                      ).hexdigest()
            for p in sorted(path.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_config_cell_generator_and_metric_are_new_files(root):
    """Adding each is a new file plus a new BENCHMARK.json entry: no file
    under bench/ that was there before changes."""
    before = _digest(root.path / "bench")
    root.write("bench/configs/tiny-new.json", tiny_config("tiny-new"))
    root.bench["configs"].append({"name": "tiny-new", "source": "test",
                                  "file": "bench/configs/tiny-new.json",
                                  "reduced": [], "why": "test"})
    root.write("bench/generators/fixed_list.py", '''
from bench.generators.requests import Planned
import numpy as np


def generate(params, *, vocab_size, seed, seconds):
    rng = np.random.default_rng(seed)
    return [Planned(i, due, rng.integers(0, vocab_size, n).astype(np.int32),
                    out)
            for i, (due, n, out) in enumerate(params["requests"])]
''')
    root.write("bench/traffic/tiny-list.json", {
        "generator": "fixed_list", "loop": "open",
        "requests": [[0.0, 40, 6], [0.3, 90, 9], [0.6, 60, 5]]})
    root.write("bench/metrics/tokens_in_window.tiny.py", '''
def read(run):
    return sum(1 for r in run.recs for t in r.token_times()
               if run.t0 < t <= run.t_end)
''')
    root.write("bench/workloads/tiny-new-list.json", tiny_cell(2, 0.2))
    root.bench["workloads"].append({"name": "tiny-new-list",
                                    "config": "tiny-new",
                                    "traffic": "tiny-list", "chips": 1,
                                    "why": "test"})
    root.bench["per_layer"].append(
        {"name": "tokens_in_window.tiny", "unit": "tokens",
         "better": "higher", "source": "program_counter", "layer": "test",
         "moves": "decode_tok_s", "workloads": ["tiny-new-list"]})
    root.save()
    after = _digest(root.path / "bench")
    assert all(after[k] == v for k, v in before.items())
    res = run(root, "tiny-new-list", seconds=1.5, trace=True)
    assert res["correct"], res["check"]
    assert res["attempted"] == 3
    assert res["metrics"]["tokens_in_window.tiny"]["value"] > 0
