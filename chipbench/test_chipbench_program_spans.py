"""The reduction of the program's own spans (``program_spans.py``): idle
gaps labelled by the innermost span, the host stall and scan fill shares,
device ops named by program and stage, the clock check, and the readers of
``host_stall_pct.bulk`` and ``scan_fill_pct.bulk`` on recorded chip traces."""

import pathlib
from types import SimpleNamespace as NS

import pytest

from chipbench import program_spans as ps
from chipbench import registry, tracing

HERE = pathlib.Path(__file__).resolve().parent
FUSION = "%fusion.1 = s32[64,20]{1,0} fusion(s32[72531,23]{1,0} %p)"
GATHER = "%all-gather.1 = s32[8,64,20]{2,1,0} all-gather(s32[2,64,20] %x)"
SCOPES = {FUSION: "jit(seq_scan)/chunk_scan/while/body/closed_call/gather:",
          GATHER: "jit(spec_scan)/merge/all_gather:"}


def ev(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def _planes():
    # window 0..12000 ns.  One call: root 200-4900 holds plan, two tiles
    # (pack, launch, wait) and finish.  Chip 0 runs tile 0's program
    # 1100-2400, tile 1's 3100-4400, then a stray copy 5500-6000; chip 1 is
    # busy all through.
    host = plane("/host:CPU", python3=[
        ev("chipbench.window", 0, 12000),
        ev("chipbench.call", 100, 5000),
        ev("repro.membership_batch", 200, 4900, docs=8, call=1),
        ev("repro.plan", 200, 600),
        ev("repro.pack", 600, 1000, tile=0, width=2048),
        ev("repro.launch", 1000, 1200, tile=0, width=2048),
        ev("repro.wait", 1200, 2600, tile=0, width=2048),
        ev("repro.pack", 2600, 3000, tile=1, width=2048),
        ev("repro.launch", 3000, 3200, tile=1, width=2048),
        ev("repro.wait", 3200, 4600, tile=1, width=2048),
        ev("repro.finish", 4600, 4900, tiles=2, rows=128, real_symbols=300,
           run_symbols=1200),
        ev("$python frame", 0, 12000)])
    chip0 = plane("/device:TPU:0", XLA_Modules=[
        ev("jit_seq_scan(42)", 1100, 2400),
        ev("jit_seq_scan(42)", 3100, 4400)],
        XLA_Ops=[ev(FUSION, 1100, 2400), ev(FUSION, 3100, 4400),
                 ev("%copy.3 = s32[8]{0} copy(s32[8]{0} %c)", 5500, 6000)])
    chip1 = plane("/device:TPU:1", XLA_Ops=[ev(GATHER, 0, 12000)])
    return [host, chip0, chip1]


@pytest.fixture
def trace():
    return ps.reduce_planes(_planes(), scopes=SCOPES)


def test_gaps_take_the_innermost_span_covering_most(trace):
    def label(lo, hi):
        return ps.gap_label(trace.spans, lo, hi)

    # the call and the root each cover over half; the root is inner (plan
    # and pack cover 400 of 1100 ns)
    assert label(0, 1100) == "repro.membership_batch"
    assert label(2400, 3100) == "repro.pack"        # 400 of 700 ns
    assert label(4400, 5500) == "chipbench.call"    # only the call: 600
    assert label(6000, 12000) == tracing.WINDOW_SPAN
    assert label(4800, 5200) == "chipbench.call"    # none over half: most
    assert sorted(n for n, _ in ps.idle_gaps(trace)) == [
        "chipbench.call", "chipbench.window", "repro.membership_batch",
        "repro.pack"]
    # the accepted reduction labels by the harness's spans alone
    old = tracing.summarize_planes(_planes())
    assert [n for n, _ in old.idle_gaps].count("chipbench.call") == 3
    assert ps.breakdown(trace)["idle_pct"] == pytest.approx(old.idle_pct)


def test_idle_split_by_innermost_open_span(trace):
    # chip 0's 8900 idle ns by the span open at each instant; chip 1 has
    # none, so each share halves in the mean over chips
    split = ps.idle_by_span(trace)
    assert split == {k: pytest.approx(v * 1e-9 / 2) for k, v in {
        tracing.WINDOW_SPAN: 100 + 500 + 6000, "repro.pack": 400 + 400,
        "repro.plan": 400, "repro.wait": 200 + 200, "repro.finish": 300,
        "chipbench.call": 100 + 100, "repro.launch": 100 + 100}.items()}
    assert sum(split.values()) == pytest.approx(8900e-9 / 2)


def test_host_stall_is_idle_inside_root_spans(trace):
    # chip 0 idles inside the root 200-1100, 2400-3100 and 4400-4900:
    # 2100 of 12000 ns; chip 1 never idles
    assert ps.host_stall_pct(trace) == pytest.approx((17.5 + 0.0) / 2)
    assert ps.breakdown(trace)["idle_pct"] == pytest.approx(
        (8900 / 12000 * 100 + 0.0) / 2)


def test_scan_fill_and_counts_from_finish_spans(trace):
    assert ps.dispatch_counts(trace) == {"tiles": 2, "rows": 128,
                                         "real_symbols": 300,
                                         "run_symbols": 1200}
    assert ps.scan_fill_pct(trace) == pytest.approx(25.0)


def test_ops_named_by_program_and_stage(trace):
    assert trace.op_s[0] == {
        "seq_scan/chunk_scan/fusion.1": pytest.approx(2600e-9),
        "copy.3": pytest.approx(500e-9)}
    assert trace.op_s[1] == {
        "spec_scan/merge/all-gather.1": pytest.approx(12000e-9)}
    # a program known only from the module line; the HLO name stays last
    assert ps.scoped_name("fusion.2", None, "jit_seq_scan(7)") == \
        "seq_scan/fusion.2"
    assert ps.scoped_name("fusion.2", None, None) == "fusion.2"
    assert ps.scoped_name("while.3", "jit(seq_scan)/chunk_scan/while",
                          "jit_other(1)") == "seq_scan/chunk_scan/while.3"


def test_clock_check_pairs_tiles_with_programs(trace):
    c = ps.clock_check(trace)
    assert c["paired"] == 2 and c["held"] == 2 and c["held_pct"] == 100.0
    assert c["start_after_launch_us"][1] == pytest.approx(0.1)


def test_without_program_spans_nothing_reads():
    planes = _planes()
    planes[0].lines[0].events = [e for e in planes[0].lines[0].events
                                 if not e.name.startswith("repro.")]
    t = ps.reduce_planes(planes)
    assert ps.host_stall_pct(t) is None
    assert ps.scan_fill_pct(t) is None
    assert ps.clock_check(t) is None


XSPACE = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 2000000 }
  }
  lines {
    id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 1500000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.9 = s32[8] fusion()"
    stats { metadata_id: 7 str_value: "jit(seq_scan)/classify/lt:" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.3 = (s32[]) while()"
    stats { metadata_id: 7 ref_value: 8 } } }
  event_metadata { key: 3 value { id: 3 name: "jit_seq_scan(5)" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8
    value { id: 8 name: "jit(seq_scan)/chunk_scan/while" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 3000000
      stats { metadata_id: 3 int64_value: 8 } }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "repro.membership_batch" } }
  stat_metadata { key: 3 value { id: 3 name: "docs" } }
}
'''


def test_scopes_read_from_serialized_event_metadata():
    from jax.profiler import ProfileData
    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    assert ps.op_scopes(data) == {
        "%fusion.9 = s32[8] fusion()": "jit(seq_scan)/classify/lt:",
        "%while.3 = (s32[]) while()": "jit(seq_scan)/chunk_scan/while"}
    t = ps.reduce_serialized(data)
    assert set(t.op_s[0]) == {"seq_scan/classify/fusion.9",
                              "seq_scan/chunk_scan/while.3"}
    assert t.spans[1].stats == {"docs": 8}
    # window 0-4000 ns, busy 1000-3000, root 500-3500
    assert ps.host_stall_pct(t) == pytest.approx(100.0 * 1000 / 4000)


def _readings(path, chips=1):
    return NS(trace=tracing.summarize(path, chips), trace_path=path,
              chips=chips)


@pytest.mark.parametrize("name", ["bulk_call", "sharded_call"])
def test_readers_read_nothing_on_a_build_without_spans(name):
    ctx = _readings(HERE / "testdata" / f"{name}.xplane.pb")
    for metric in ("host_stall_pct.bulk", "scan_fill_pct.bulk"):
        assert registry.metric_reader(metric)(ctx) is None
    assert registry.metric_reader("host_stall_pct.bulk")(
        NS(trace=None, chips=1)) is None


RECORDED = HERE / "testdata" / "prosite20_scan.xplane.pb"


def test_recorded_prosite_trace_reads_what_the_chip_run_printed():
    # one call of prosite20-scan (seed 1300000031, --seconds 0.05 --trace 1)
    # on one TPU v5e, trimmed to chip 0's XLA Ops and XLA Modules lines
    # (with each op's tf_op stat) and the chipbench.* / repro.* host spans
    ctx = _readings(RECORDED)
    assert registry.metric_reader("scan_fill_pct.bulk")(ctx) == \
        pytest.approx(24.19422290943287)
    assert registry.metric_reader("host_stall_pct.bulk")(ctx) == \
        pytest.approx(11.877197095955056)
    # the accepted metrics read as the run printed them
    ctx.counters, ctx.config = {"bytes": 214055}, {"patterns": [0] * 20}
    ctx.device_kind = "TPU v5 lite"
    assert registry.metric_reader("device_idle_pct.bulk")(ctx) == \
        pytest.approx(13.072079686523308)
    assert registry.metric_reader("match_roofline_pct.bulk")(ctx) == \
        pytest.approx(0.013703450857169324)


def test_recorded_prosite_trace_spans_and_names():
    t = ps.load(RECORDED)
    # the window's real symbols are the harness's bytes for the call
    assert ps.dispatch_counts(t) == {"tiles": 8, "rows": 512,
                                     "real_symbols": 214055,
                                     "run_symbols": 884736}
    clock = ps.clock_check(t)
    assert clock["paired"] == 8 and clock["held_pct"] == 100.0
    ops = dict(ps.breakdown(t)["device_ops"])
    assert ops["seq_scan/chunk_scan/fusion.17"] == pytest.approx(
        0.13976734299997523)
    assert ops["seq_scan/classify/fusion.2"] == pytest.approx(
        0.008608777000000001)
    # gaps over 1 ms: the window's first (before the first program: the
    # call covers most of it, the root less than half) and each tile's,
    # most of which the host spends fetching that tile's outputs
    gaps = ps.idle_gaps(t)
    assert [n for n, s in gaps if s > 1e-3] == ["chipbench.call"] + \
        ["repro.wait"] * 8
    split = ps.idle_by_span(t)
    assert max(split, key=split.get) == "repro.wait"
