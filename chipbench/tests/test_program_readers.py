"""The readers of the program's own spans and scopes: on a hand-built trace,
on a trace with none (which must give nothing), and on a small trace
recorded on a TPU v5e (``record_program_trace.py``) where it is present.
The readers that were there before read what they read before."""

import gzip
from pathlib import Path
import shutil

import pytest

from chipbench import program_spans as ps
from chipbench import trace_reduce as tr
from chipbench.tests.test_metrics import read
from chipbench.tests.test_trace_reduce import MS, RECORDED, hand_built

PROGRAM = Path(__file__).parent / "data" / "program.xplane.pb.gz"


def with_program() -> tr.Trace:
    # busy: device 0 0-30 and 40-70 ms, device 1 0-50 and 60-80 ms, window 0-100
    t = hand_built()
    t.program = [
        (20 * MS, 45 * MS, "repro.serve.decode_tick", {"occupied": 3, "max_slots": 4}),
        (25 * MS, 45 * MS, "repro.runtime.iteration", {"plan": "p", "step_num": 0}),
        (30 * MS, 41 * MS, "repro.serve.prefill.request", {"prompt_len": 256, "new_program": 0}),
        (60 * MS, 95 * MS, "repro.serve.decode_tick", {"occupied": 1, "max_slots": 4}),
        (65 * MS, 90 * MS, "repro.runtime.iteration", {"plan": "p", "step_num": 1}),
        (95 * MS, 105 * MS, "repro.serve.decode_tick", {"occupied": 4, "max_slots": 4}),
    ]
    t.ops = [(30, "jit(step)/while/body/attention/dot_general:dot"),
             (10, "jit(step)/transpose(jvp(attention))/mul:mul"),
             (60, "jit(step)/while/body/mlp/dot_general:dot"),
             (5, "")]
    return t


def test_idle_inside_intervals_averages_devices_and_clips_to_window():
    t = with_program()
    got = ps.idle_inside_ns(t, [(20 * MS, 45 * MS), (-10 * MS, 5 * MS), (85 * MS, 120 * MS),
                                (100 * MS, 110 * MS)])
    # 20-45: device 0 idle 30-40, device 1 busy throughout; 85-100 idle on both
    assert got == [5 * MS, 0.0, 15 * MS, 0.0]


def test_named_keeps_spans_inside_the_window():
    t = with_program()
    assert [sp[:2] for sp in ps.named(t, "repro.serve.decode_tick")] == [
        (20 * MS, 45 * MS), (60 * MS, 95 * MS)
    ]


def test_scope_matching():
    assert ps.in_scope("jit(step)/jvp()/while/body/closed_call/attention/dot_general:dot", "attention")
    assert ps.in_scope("transpose(jvp(attention))/mul", "attention")
    assert ps.in_scope("jit(step)/attention", "attention")
    assert not ps.in_scope("jit(step)/attention_mask/mul", "attention")
    assert not ps.in_scope("", "attention")


def test_new_readers_on_a_hand_built_trace():
    t = with_program()
    # ticks: 5 ms idle in 20-45, (25 + 15) / 2 in 60-95
    assert read("decode_host_idle_ms.serve", trace=t) == pytest.approx(12.5)
    assert read("decode_occupancy.serve", trace=t) == pytest.approx(50.0)
    assert read("prefill_host_idle_ms.serve", trace=t) == pytest.approx(5.0)
    # steps: 5 ms idle in 25-45, (20 + 10) / 2 in 65-90
    assert read("runtime_idle_ms.train", trace=t) == pytest.approx(10.0)
    assert read("attention_share.train", trace=t) == pytest.approx(40.0 / 105 * 100)


NEW = ["decode_host_idle_ms.serve", "prefill_host_idle_ms.serve", "decode_occupancy.serve",
       "runtime_idle_ms.train", "attention_share.train"]


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_without_program_spans(name):
    t = hand_built()
    t.program, t.ops = [], [(30, "jit(step)/mlp/dot:dot")]
    assert read(name, trace=t) is None


@pytest.mark.parametrize("name", NEW)
def test_a_loaded_trace_with_no_file_to_read_gives_nothing(name, tmp_path, monkeypatch):
    monkeypatch.setattr(ps, "TRACES", tmp_path / "absent")
    assert read(name, trace=hand_built()) is None


def test_old_readers_read_what_they_read_before():
    t = with_program()
    assert read("device_idle_share.train", trace=t) == pytest.approx(35.0)
    assert read("device_idle_share.serve", trace=t) == pytest.approx(35.0)
    assert read("step_gap_ms.train", trace=t) == pytest.approx(10.0)
    assert t.breakdown() == hand_built().breakdown()


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_old_readers_on_the_recorded_trace(tmp_path, monkeypatch):
    """The values the readers gave on ``small.xplane.pb`` before the program
    had spans; that trace has none of the program's, so the new readers
    give nothing on it."""
    from jax.profiler import ProfileData

    t = tr.from_profile(ProfileData.from_file(str(RECORDED)))
    assert read("device_idle_share.train", trace=t) == pytest.approx(99.25062473740284, rel=1e-12)
    assert read("device_idle_share.serve", trace=t) == pytest.approx(99.25062473740284, rel=1e-12)
    assert read("step_gap_ms.train", trace=t) == pytest.approx(3.135495, rel=1e-12)
    b = t.breakdown()
    assert b["device_ops"] == [["fusion", pytest.approx(3.7842e-05)],
                               ["convolution_tanh_fusion", pytest.approx(3.4719e-05)],
                               ["copy-start", pytest.approx(4e-08)],
                               ["copy-done", pytest.approx(8e-09)]]
    assert [n for n, _ in b["idle_gaps"]] == ["chipbench.train.data"] * 10
    assert b["idle_gaps"][0][1] == pytest.approx(0.003208079)
    (tmp_path / "run").mkdir()
    shutil.copy(RECORDED, tmp_path / "run" / "small.xplane.pb")
    monkeypatch.setattr(ps, "TRACES", tmp_path)
    assert ps.of(t) == [] and len(ps.leaf_ops(t)) == 12
    for name in NEW:
        assert read(name, trace=t) is None


@pytest.mark.skipif(not PROGRAM.exists(), reason="no recorded program trace")
def test_program_spans_land_on_the_host_planes(tmp_path, monkeypatch):
    """A tiny training step and serving round traced on a TPU v5e
    (``record_program_trace.py``): the spans are on the ``/host:`` planes
    the reduction reads, on the device timeline's clock."""
    (tmp_path / "run").mkdir()
    path = tmp_path / "run" / "program.xplane.pb"
    path.write_bytes(gzip.decompress(PROGRAM.read_bytes()))
    t = tr.load(tmp_path)
    monkeypatch.setattr(ps, "TRACES", tmp_path)
    names = {sp[2] for sp in ps.of(t)}
    assert {"repro.runtime.iteration", "repro.runtime.feed", "repro.runtime.launch",
            "repro.runtime.sync", "repro.runtime.program", "repro.serve.decode_tick",
            "repro.serve.decode.emit", "repro.serve.prefill.request",
            "repro.serve.prefill.insert", "repro.serve.release"} <= names
    requests = ps.named(t, "repro.serve.prefill.request")
    assert [sp[3]["new_program"] for sp in requests] == [0, 0, 1]
    assert [sp[3]["occupied"] for sp in ps.named(t, "repro.serve.decode_tick")] == [3, 3]
    assert read("decode_occupancy.serve", trace=t) == pytest.approx(75.0)
    for name in ("decode_host_idle_ms.serve", "prefill_host_idle_ms.serve",
                 "runtime_idle_ms.train", "attention_share.train"):
        value = read(name, trace=t)
        assert value is not None and value >= 0, name
    assert 0 < read("attention_share.train", trace=t) < 100
