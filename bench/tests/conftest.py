"""CPU tests of the benchmark harness (run by path:
``python -m pytest bench/tests``). They build a scratch root holding a
copy of ``bench/`` plus tiny cells, and drive the harness on the CPU."""
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# compiled programs of these runs go to a scratch cache, not the checkout's
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-tests-jax-cache-"))

TINY_PARISKV = {
    "m": 8, "magnitude_bits": 3, "rho": 0.25, "beta": 0.08,
    "tier_weights": [6, 5, 4, 3, 2, 1],
    "tier_pcts": [0.05, 0.15, 0.30, 0.50, 0.75, 1.00], "top_k": 16,
    "min_candidates": 32, "max_candidates": 4096, "sink_size": 8,
    "local_size": 32, "update_interval": 16, "srht_seed": 10098965,
    "hist_sample": 0}


def tiny_config(name: str, dtype: str = "bfloat16") -> dict:
    return {"name": name, "source": "test", "program_config": "qwen2-1.5b",
            "hidden_size": 256, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 64, "intermediate_size": 512, "vocab_size": 512,
            "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "tie_word_embeddings": False, "use_qkv_bias": True,
            "torch_dtype": dtype, "pariskv": TINY_PARISKV}


def tiny_cell(batch: int, limit: float) -> dict:
    return {"engine": {"n_max": 256, "max_batch": batch, "block_size": 16,
                       "chunk_size": 4, "prefill_budget": 32,
                       "num_blocks": 16 * batch},
            "trace": {"after_s": 0.5, "seconds": 1.0},
            "correctness": {"requests": 3, "max_tokens": 256,
                            "min_tokens": 8,
                            "limits": {"logit_gap": limit}}}


TRAFFIC = {
    "tiny-closed": {"generator": "requests", "loop": "closed",
                    "concurrency": 1, "fill_in_setup": True, "requests": 2,
                    "prompt_len": {"fixed": 150},
                    "output_len": {"fixed": 60}, "max_total": 256},
    "tiny-open": {"generator": "requests", "loop": "open",
                  "rate_per_s": 4.0,
                  "prompt_len": {"lognormal": {"median": 80, "sigma": 0.7,
                                               "min": 20, "max": 200}},
                  "output_len": {"lognormal": {"median": 12, "sigma": 0.8,
                                               "min": 4, "max": 40}},
                  "max_total": 256},
}


class Root:
    """A scratch checkout: ``bench/`` copied, ``src`` linked, and helpers
    that add files and ``BENCHMARK.json`` entries."""

    def __init__(self, path: Path):
        self.path = path
        shutil.copytree(REPO / "bench", path / "bench",
                        ignore=shutil.ignore_patterns("tests", "__pycache__"))
        (path / "src").symlink_to(REPO / "src")
        self.bench = json.loads((REPO / "BENCHMARK.json").read_text())
        peaks = json.loads((path / "bench" / "peaks.json").read_text())
        peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
        self.write("bench/peaks.json", peaks)
        self.save()

    def write(self, rel: str, obj) -> None:
        p = self.path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(obj if isinstance(obj, str) else json.dumps(obj))

    def save(self) -> None:
        self.write("BENCHMARK.json", json.dumps(self.bench, indent=1))

    def add_cell(self, name: str, config: str, traffic: str,
                 cell: dict) -> None:
        if not (self.path / f"bench/configs/{config}.json").exists():
            self.write(f"bench/configs/{config}.json", tiny_config(config))
            self.bench["configs"].append(
                {"name": config, "source": "test",
                 "file": f"bench/configs/{config}.json", "reduced": [],
                 "why": "test"})
        if not (self.path / f"bench/traffic/{traffic}.json").exists():
            self.write(f"bench/traffic/{traffic}.json", TRAFFIC[traffic])
        self.write(f"bench/workloads/{name}.json", cell)
        self.bench["workloads"].append(
            {"name": name, "config": config, "traffic": traffic,
             "chips": 1, "why": "test"})
        for m in self.bench["end_to_end"] + self.bench["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
        self.save()


@pytest.fixture
def root(tmp_path):
    return Root(tmp_path)


def run(root: Root, workload: str, seed: int = 7, seconds: float = 2.0,
        trace: bool = False, out=None, **kw) -> dict:
    """One run on the CPU; its printed lines go to ``out`` where given."""
    import io
    import time
    from bench.harness import run_cell
    buf = out if out is not None else io.StringIO()
    res = run_cell(root.path, workload, seed, seconds, trace,
                   time.perf_counter(), require_tpu=False, out=buf, **kw)
    last = buf.getvalue().strip().split("\n")[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res
