"""Each per-layer metric reader on hand-built inputs, and the rule that a
reader with nothing to read returns nothing."""

import json
from pathlib import Path

import pytest

from chipbench import flops
from chipbench.run import HERE, load_module
from chipbench.tests.test_trace_reduce import hand_built

PEAKS = json.loads((HERE / "peaks.json").read_text())["devices"]["TPU v5 lite"]
CONFIG = json.loads((HERE / "configs" / "gpt-medium.json").read_text())
TRAFFIC = {"seq_len": 1024}


def reader(name):
    return load_module(HERE / "metrics" / f"{name}.py", f"test_metric_{name}")


def read(name, summary=None, trace=None, chips=1):
    return reader(name).read(summary=summary or {}, trace=trace, peaks=PEAKS, flops=flops,
                             config=CONFIG, traffic=TRAFFIC, chips=chips)


def test_every_metric_has_a_reader():
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").exists(), m["name"]


@pytest.mark.parametrize("name", sorted(p.stem for p in (HERE / "metrics").glob("*.py")))
def test_nothing_to_read_gives_nothing(name):
    assert read(name) is None


def test_flops_per_token_of_gpt_medium():
    assert flops.matmul_params(CONFIG) == 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 50257 * 1024
    assert flops.train_flops_per_token(CONFIG, 1024) == pytest.approx(2.4224e9, rel=1e-3)


def test_train_step_mfu():
    tps = 20_000.0
    want = 100 * flops.train_flops_per_token(CONFIG, 1024) * tps / 197e12
    assert read("train_step_mfu", {"untraced_tokens_per_s": tps}) == pytest.approx(want)
    assert read("train_step_mfu", {"untraced_tokens_per_s": tps}, chips=4) == pytest.approx(want / 4)


def test_trace_metrics():
    t = hand_built()
    assert read("device_idle_share.train", trace=t) == pytest.approx(35.0)
    assert read("device_idle_share.serve", trace=t) == pytest.approx(35.0)
    assert read("step_gap_ms.train", trace=t) == pytest.approx(10.0)


def test_serving_host_metrics():
    summary = {"ticks": [(0.05, 32, 10_000), (0.07, 16, 5_000), (0.06, 8, 100)],
               "prefills": [(0.02, 256), (0.06, 1024)], "param_bytes": 4 * 353_000_000,
               "kv_itemsize": 2}
    assert read("decode_tick_ms.serve", summary) == pytest.approx(60.0)
    assert read("prefill_us_per_token.serve", summary) == pytest.approx(0.08 / 1280 * 1e6)
    share = read("decode_step_mfu.serve", summary)
    least = sum(
        max(flops.decode_flops(CONFIG, o, p) / 197e12,
            flops.decode_bytes(CONFIG, summary["param_bytes"], 2, p) / 819e9)
        for _, o, p in summary["ticks"]
    )
    assert share == pytest.approx(100 * least / 0.18)
    assert 0 < share < 100
