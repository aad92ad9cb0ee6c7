"""The trace reduction on a small trace recorded on a TPU v5e (PR 24's
chip call: two jitted programs run six times each over 0.37 s)."""

import os
import shutil

import pytest

import tracereduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    spans = [(0, 10), (5, 20), (30, 40), (32, 35), (100, 101)]
    assert tracereduce.union_length_ns(spans) == 31
    assert tracereduce.gaps_ns(spans) == [60, 10]
    assert tracereduce.union_length_ns([]) == 0
    out = tracereduce.reduce_planes({
        "/device:TPU:0": [("a", 0, 10), ("b", 20, 40)],
        "/device:TPU:1": [("a", 0, 30)]})
    assert out["planes"] == 2
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["device_ops"][0] == ["a", pytest.approx(40e-9)]
    assert tracereduce.reduce_planes({})["busy_s"] == 0.0


def test_recorded_tpu_trace(tmp_path):
    run_dir = tmp_path / "plugins" / "profile" / "2026_09_27"
    run_dir.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "tpu_probe.xplane.pb"),
                run_dir / "host.xplane.pb")
    out = tracereduce.reduce_trace(str(tmp_path))
    assert out["planes"] == 1
    # 12 program runs of a few microseconds each in 0.37 s: busy in the
    # tens of microseconds, far under the traced stretch.
    assert 10e-6 < out["busy_s"] < 500e-6
    names = [name for name, _ in out["device_ops"]]
    assert any("fusion" in name for name in names)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][1] > 0.01
    # the CPU-rehearsal selection finds nothing on a device plane
    assert tracereduce.reduce_trace(str(tmp_path), "/host:CPU",
                                    "tf_XLA")["planes"] == 0
