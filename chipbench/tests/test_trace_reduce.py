"""The trace reduction on a hand-built trace, and on a small trace recorded
on a TPU v5e (``record_trace.py``) where it is present."""

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

MS = 1_000_000


def hand_built() -> tr.Trace:
    # window 0..100 ms on two devices; device 0 runs step programs 0-30 and
    # 40-70 (ops inside), device 1 has a collective 70-80 that overlaps
    # compute for 70-75 only
    d0 = tr.Device(
        ops=[(0, 10 * MS, "fusion.1"), (10 * MS, 30 * MS, "convolution.2"),
             (40 * MS, 70 * MS, "fusion.1")],
        modules=[(0, 30 * MS, "jit_step"), (40 * MS, 70 * MS, "jit_step"),
                 (90 * MS, 91 * MS, "jit_small")],
    )
    d1 = tr.Device(
        ops=[(0, 50 * MS, "fusion.7"), (60 * MS, 75 * MS, "fusion.8"),
             (70 * MS, 80 * MS, "collective-permute-done.3")],
        modules=[],
    )
    host = [(0, 100 * MS, "chipbench.window"), (30 * MS, 41 * MS, "chipbench.train.data"),
            (41 * MS, 70 * MS, "chipbench.train.step")]
    return tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, host)


def test_busy_idle_and_window():
    t = hand_built()
    assert t.window_s() == pytest.approx(0.1)
    assert tr.total(t.busy_intervals("/device:TPU:0")) == 60 * MS
    assert tr.total(t.busy_intervals("/device:TPU:1")) == 70 * MS
    assert t.busy_s() == pytest.approx(0.065)
    assert t.idle_share() == pytest.approx(0.35)


def test_collectives_and_exposure():
    t = hand_built()
    assert tr.is_collective("all-reduce.12") and tr.is_collective("collective-permute-start")
    assert not tr.is_collective("fusion.all-reduce")
    assert tr.total(t.busy_intervals("/device:TPU:1", "collective")) == 10 * MS
    # device 1: 5 of its 10 collective ms have no compute beside them; device 0 none
    assert t.exposed_collective_s() == pytest.approx(0.0025)


def test_gaps_are_labelled_by_host_span():
    t = hand_built()
    assert t.gaps("/device:TPU:0") == [(30 * MS, 40 * MS), (70 * MS, 100 * MS)]
    gaps = t.labelled_gaps()
    assert gaps[0] == ("host", pytest.approx(0.03))
    assert ("chipbench.train.data", pytest.approx(0.01)) in gaps


def test_step_gaps_use_the_main_program():
    t = hand_built()
    assert [r[2] for r in t.module_runs("/device:TPU:0")] == ["jit_step", "jit_step"]
    assert t.step_gaps_s("/device:TPU:0") == [pytest.approx(0.01)]
    assert t.step_gaps_s("/device:TPU:1") == []


def test_breakdown_lists_top_ops_and_gaps():
    b = hand_built().breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert names[:2] == ["fusion.7", "fusion.1"] and len(b["device_ops"]) <= tr.TOP
    assert b["idle_gaps"][0][0] == "host"


def test_merge_and_intersect():
    assert tr.merge([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    assert tr.intersect([(0, 10)], [(2, 3), (5, 12)]) == [(2, 3), (5, 10)]


RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    from jax.profiler import ProfileData

    t = tr.from_profile(ProfileData.from_file(str(RECORDED)))
    assert t is not None and len(t.devices) == 1
    dev = next(iter(t.devices))
    assert t.devices[dev].ops and t.devices[dev].modules
    names = {n for _, _, n in t.host}
    assert {"chipbench.window", "chipbench.train.step", "chipbench.train.data"} <= names
    assert 0.0 < t.busy_s() < t.window_s()
    # three executions of the one program, separated by host pauses
    assert len(t.module_runs(dev)) == 3
    assert len(t.step_gaps_s(dev)) == 2 and all(g > 0 for g in t.step_gaps_s(dev))
    assert any(label == "chipbench.train.data" for label, _ in t.labelled_gaps())
