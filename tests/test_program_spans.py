"""The matcher's profiler spans, read back from a trace taken on the CPU.

A ``Matcher.membership_batch`` run under ``jax.profiler`` writes, on the
host plane of the xplane: the root span ``repro.membership_batch`` (kwargs
``docs`` and ``call``) holding ``repro.plan``, one ``repro.pack`` /
``repro.launch`` / ``repro.wait`` per tile (kwargs ``tile`` and ``width``)
and ``repro.finish`` with the call's counts.  ``repro.compile`` sits in the
launch of a newly lowered program's first call only.  Every profiler test
of the matcher lives in this file.
"""

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import Matcher, compile_regex, make_search_dfa

HOST_PLANE = "/host:CPU"


def _spans(trace_dir):
    path = next(trace_dir.rglob("*.xplane.pb"))
    plane = ProfileData.from_file(str(path)).find_plane_with_name(HOST_PLANE)
    out = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
           for line in plane.lines for e in line.events
           if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two identical calls, each in a trace of its own: 7 documents in
    tiles of 4, one seq bucket of width 512."""
    m = Matcher([make_search_dfa(compile_regex(p))
                 for p in (".*ab+c", ".*[0-9]{3}")], num_chunks=1,
                batch_tile=4)
    rng = np.random.default_rng(5)
    docs = [bytes(rng.choice(list(b"abcx019"), size=n).astype(np.uint8))
            for n in (3, 40, 300, 17, 500, 0, 64)]
    calls = []
    for i in range(2):
        d = tmp_path_factory.mktemp(f"trace{i}")
        with jax.profiler.trace(str(d)):
            m.membership_batch(docs)
        calls.append(_spans(d))
    return m, docs, calls


def test_call_writes_root_plan_and_tile_spans(traced):
    m, docs, calls = traced
    spans = calls[0]
    roots = [s for s in spans if s[0] == "repro.membership_batch"]
    assert len(roots) == 1
    root = roots[0]
    assert root[3]["docs"] == len(docs) and root[3]["call"] == 1
    inner = [s for s in spans if s is not root]
    assert all(_inside(s, root) for s in inner)
    names = [s[0] for s in inner if s[0] != "repro.compile"]
    assert names == ["repro.plan"] + ["repro.pack", "repro.launch",
                                      "repro.wait"] * 2 + ["repro.finish"]
    for n in ("repro.pack", "repro.launch", "repro.wait"):
        tiles = [s[3] for s in inner if s[0] == n]
        assert tiles == [{"tile": 0, "width": 512}, {"tile": 1, "width": 512}]
    finish = next(s[3] for s in inner if s[0] == "repro.finish")
    assert finish["tiles"] == 2 and finish["rows"] == 8
    assert finish["real_symbols"] == sum(map(len, docs))
    # tiles longest first: (500, 300, 64, 40) and (17, 3, 0)
    assert finish["bound_symbols"] == 4 * 500 + 4 * 17
    # finish's counts are the ones perf_report accumulates (two calls)
    rep = m.perf_report()["dispatch"]
    for key in ("real_symbols", "bound_symbols", "run_symbols"):
        assert rep[key] == 2 * finish[key]


def test_spans_follow_each_other_per_tile(traced):
    _, _, calls = traced
    tile = [s for s in calls[0] if s[0] in ("repro.pack", "repro.launch",
                                            "repro.wait")]
    ends = [s[2] for s in tile]
    starts = [s[1] for s in tile]
    assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_compile_span_on_first_call_only(traced):
    _, _, calls = traced
    first = [s for s in calls[0] if s[0] == "repro.compile"]
    assert len(first) == 1  # one bucket shape: one new program
    launch0 = next(s for s in calls[0] if s[0] == "repro.launch")
    assert _inside(first[0], launch0)
    second = calls[1]
    assert not [s for s in second if s[0] == "repro.compile"]
    root = next(s for s in second if s[0] == "repro.membership_batch")
    assert root[3]["call"] == 2
