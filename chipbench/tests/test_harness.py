"""The harness: it refuses to run without a TPU or outside a full checkout,
knows only the peaks it has, and finds a new configuration, traffic mix and
metric by name with no file of the benchmark edited."""

import json
import os
from pathlib import Path
import shutil
import subprocess
import sys

import pytest

from chipbench import generate
from chipbench.run import HERE, ROOT, find_cell, load_module, metrics_for

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd: Path, *extra, env=None):
    cell = BENCH["workloads"][0]["name"]
    cmd = [sys.executable, "chipbench/run.py", "--workload", cell, "--seed", str(2**40 + 1),
           "--seconds", "1", *extra]
    return subprocess.run(cmd, cwd=cwd, capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_refuses_without_a_tpu():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_peaks_know_the_v5e_only_by_its_kind():
    peaks = json.loads((HERE / "peaks.json").read_text())
    assert peaks["devices"]["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert "TPU v4" not in peaks["devices"] and peaks["source"]


def test_every_cell_is_found_and_reports_what_its_metrics_move():
    for cell in BENCH["workloads"]:
        found, config, traffic = find_cell(BENCH, cell["name"])
        assert (HERE / "drivers" / f"{traffic['driver']}.py").exists()
        assert config["name"] == cell["config"]
        e2e = {m["name"] for m in metrics_for(BENCH, cell["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = metrics_for(BENCH, cell["name"], "per_layer")
        assert layer and all(m["moves"] in e2e for m in layer)


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds files and manifest entries only."""
    root = tmp_path / "repo"
    shutil.copytree(HERE, root / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "configs" / "gpt-small.json").write_text(
        json.dumps(dict(json.loads((HERE / "configs" / "gpt-medium.json").read_text()),
                        name="gpt-small", hidden_size=768)))
    (root / "chipbench" / "traffic" / "long.json").write_text(
        json.dumps({"driver": "serve", "rate": 1.0}))
    (root / "chipbench" / "metrics" / "queue_wait_ms.serve.py").write_text(
        "def read(*, summary, **_):\n    return summary.get('queue_wait_ms')\n")
    bench["configs"].append({"name": "gpt-small", "source": "https://example.org",
                             "file": "chipbench/configs/gpt-small.json", "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gpt-small.serve1.long", "config": "gpt-small",
                               "traffic": "long", "chips": 1, "why": "x"})
    next(m for m in bench["end_to_end"] if m["name"] == "serve_output_tokens_per_s")["workloads"].append(
        "gpt-small.serve1.long")
    bench["per_layer"].append({"name": "queue_wait_ms.serve", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "server", "moves": "serve_output_tokens_per_s",
                               "workloads": ["gpt-small.serve1.long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    run = load_module(root / "chipbench" / "run.py", "chipbench_run_copy")
    cell, config, traffic = run.find_cell(bench, "gpt-small.serve1.long")
    assert config["hidden_size"] == 768 and traffic["rate"] == 1.0
    names = [m["name"] for m in run.metrics_for(bench, "gpt-small.serve1.long", "per_layer")]
    assert names == ["queue_wait_ms.serve"]
    m = run.load_module(root / "chipbench" / "metrics" / "queue_wait_ms.serve.py", "m")
    assert m.read(summary={"queue_wait_ms": 3.0}) == 3.0
    # nothing that was there changed
    for p in HERE.rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (root / "chipbench" / p.relative_to(HERE)).read_bytes() == p.read_bytes()


def test_schedule_gives_every_seed_the_same_work():
    traffic = json.loads((HERE / "traffic" / "chat.json").read_text())
    a = generate.serve_schedule(traffic, 2**40 + 1, 40.0)
    b = generate.serve_schedule(traffic, 2**40 + 2, 40.0)
    assert len(a) == len(b) == round(traffic["rate"] * 40.0)
    for field in ("prompt_len", "new_tokens"):
        assert sorted(getattr(r, field) for r in a) == sorted(getattr(r, field) for r in b)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert all(0 <= r.due < 40.0 for r in a) and len({r.rid for r in a}) == len(a)
    assert a == generate.serve_schedule(traffic, 2**40 + 1, 40.0)
    assert set(r.prompt_len for r in a) <= set(traffic["prompt"]["buckets"])
    assert all(traffic["output"]["min"] <= r.new_tokens <= traffic["output"]["max"] for r in a)


def test_train_batches_differ_by_step_and_seed():
    traffic = json.loads((HERE / "traffic" / "train-1stage-m8.json").read_text())
    t0, l0 = generate.train_batch(traffic, 50257, 2**40 + 1, 0)
    t1, _ = generate.train_batch(traffic, 50257, 2**40 + 1, 1)
    t2, _ = generate.train_batch(traffic, 50257, 2**40 + 2, 0)
    assert t0.shape == (8, 1, 1024) and (t0[:, :, 1:] == l0[:, :, :-1]).all()
    assert (t0 != t1).any() and (t0 != t2).any()
    assert len({row.tobytes() for row in t0.reshape(8, -1)}) == 8


def test_quantile_counts_a_miss_as_infinite():
    assert generate.quantile([1.0, 2.0, 3.0, 4.0, 5.0], 0.5) == 3.0
    assert generate.quantile([1.0, 2.0, 3.0, 4.0, 5.0], 0.95) == pytest.approx(4.8)
    assert generate.quantile([1.0] * 19 + [float("inf")], 0.95) == float("inf")
