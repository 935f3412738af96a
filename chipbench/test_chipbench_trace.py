"""The trace reduction (busy union, idle share, all-gather share, idle gaps)
and the roofline arithmetic."""

import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import roofline, tracing

HERE = pathlib.Path(__file__).resolve().parent


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def _planes():
    # window 1000..2000 ns; chip 0: fusion 900-1300 (clipped to 1000-1300)
    # overlapping all-gather 1200-1500, then a gap to 1800-1900;
    # chip 1: one all-gather 1000-1500
    host = plane("/host:CPU", python=[
        ev("chipbench.window", 1000, 1000),
        ev("chipbench.call", 1000, 500),
        ev("chipbench.wait", 1500, 400),
        ev("not.ours", 1500, 500)])
    chip0 = plane("/device:TPU:0", XLA_Ops=[
        ev("fusion.1", 900, 400), ev("all-gather.3", 1200, 300),
        ev("fusion.1", 1800, 100)], XLA_Modules=[ev("jit_run", 0, 5000)])
    chip1 = plane("/device:TPU:1", XLA_Ops=[ev("all-gather.3", 1000, 500)])
    other = plane("/device:TPU:0 SparseCore", XLA_Ops=[ev("x", 0, 9000)])
    return [host, chip0, chip1, other]


def test_busy_union_idle_and_shares():
    s = tracing.summarize_planes(_planes())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx([600e-9, 500e-9])
    assert s.idle_pct == pytest.approx((40.0 + 50.0) / 2)
    assert s.busy_max_s == pytest.approx(600e-9)
    # chip 0: 300 of 600 busy ns in all-gather; chip 1: all of it
    assert s.share_pct("all-gather") == pytest.approx((50.0 + 100.0) / 2)
    assert s.share_pct("no-such-op") is None


def test_idle_gaps_are_labelled_by_host_span():
    s = tracing.summarize_planes(_planes())
    # chip 0 idles 1500-1800 (inside chipbench.wait) and 1900-2000
    # (wait 1500-1900 ends; nothing of ours after); chip 1 idles 1500-2000
    assert s.idle_gaps[0] == ("chipbench.wait", pytest.approx(500e-9))
    assert ("chipbench.wait", pytest.approx(300e-9)) in s.idle_gaps
    assert ("chipbench.window", pytest.approx(100e-9)) in s.idle_gaps
    b = s.breakdown()
    assert b["device_ops"][0] == ["all-gather.3", pytest.approx(400e-9)]
    assert len(b["idle_gaps"]) == 3


def test_only_the_chips_a_run_used_count():
    s = tracing.summarize_planes(_planes(), chips=1)
    assert s.chips == 1 and s.busy_s == pytest.approx([600e-9])


def test_trace_without_window_or_device_is_refused():
    host, chip0 = _planes()[:2]
    with pytest.raises(ValueError, match="chipbench.window"):
        tracing.summarize_planes([chip0])
    with pytest.raises(ValueError, match="device"):
        tracing.summarize_planes([host])


def test_union_merges_overlaps():
    import numpy as np
    iv = np.array([[5, 7], [0, 2], [1, 3], [3, 4]], float)
    assert tracing.union(iv).tolist() == [[0, 4], [5, 7]]
    assert tracing.gaps(tracing.union(iv), -1, 9).tolist() == [
        [-1, 0], [4, 5], [7, 9]]


def test_roofline_bytes_and_time():
    assert roofline.match_bytes(1000, 14) == 1000 * 57
    t = roofline.match_min_seconds(819_000, 1, 1, "TPU v5 lite")
    assert t == pytest.approx(819_000 * 5 / 819e9)
    assert roofline.match_min_seconds(819_000, 1, 4, "TPU v5 lite") == \
        pytest.approx(t / 4)
    with pytest.raises(LookupError):
        roofline.peak("TPU v9 imaginary")


def test_recorded_chip_trace():
    # two pcre14 spec calls of 8 short documents, traced on one TPU v5e
    # (trimmed to the XLA Ops line and the harness's host spans)
    s = tracing.summarize(HERE / "testdata" / "bulk_call.xplane.pb")
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.474347686)
    assert s.busy_s == pytest.approx([0.466032883])
    assert s.idle_pct == pytest.approx(1.7528920758770217)
    assert s.share_pct("all-gather") is None
    ops = dict(s.breakdown()["device_ops"])
    # the scan loop and the gather inside it: nested events, each counted
    assert ops["while.9"] == pytest.approx(0.463516762)
    assert ops["fusion.44"] == pytest.approx(0.454558013, rel=1e-6)
    assert s.idle_gaps[0][0] == "chipbench.call"


def test_op_name_is_the_hlo_name():
    assert tracing.op_name("%fusion.44 = s32[107520]{0} fusion(x)") == \
        "fusion.44"
    assert tracing.op_name("all-gather-start.1") == "all-gather-start.1"


def test_recorded_four_chip_trace():
    # two pcre14 calls on the sharded backend's (1, 4) mesh, traced on a
    # 2x2 TPU v5e host: the chunk-axis all_gather shows on every chip
    s = tracing.summarize(HERE / "testdata" / "sharded_call.xplane.pb")
    assert s.chips == 4
    assert s.window_s == pytest.approx(0.120763882)
    assert s.busy_s == pytest.approx([0.110085963, 0.110083856,
                                      0.110083764, 0.11008226])
    assert s.idle_pct == pytest.approx(8.843638572334063)
    assert s.share_pct("all-gather") == pytest.approx(0.033783280427537234)
    assert all(any(n.startswith("all-gather") for n in ops) for ops in s.op_s)
