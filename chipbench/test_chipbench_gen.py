"""The traffic generators: the same seed gives the same bytes, and every
seed gives a closed-loop call the same multiset of lengths."""

import numpy as np
import pytest

from chipbench import gen, registry

BIG_SEED = 2**31 + 12345  # wider than 32 signed bits: seeds may be that large
TEST_MIXES = registry.HERE / "testdata" / "traffic"


@pytest.fixture(autouse=True)
def _test_mixes(monkeypatch):
    monkeypatch.setattr(registry, "TRAFFIC_DIRS",
                        [*registry.TRAFFIC_DIRS, TEST_MIXES])


def _tiny(name, **kw):
    return {**registry.traffic(name), **kw}


def _make(mix, seed, seconds=1.0):
    return registry.generator(mix["generator"])(mix, seed, seconds)


def test_closed_batches_repeat_per_seed():
    mix = _tiny("logs_1k_16k", docs_per_call=6, max_bytes=4096, pool_calls=2)
    a = _make(mix, BIG_SEED)
    assert a == _make(mix, BIG_SEED)
    assert a != _make(mix, BIG_SEED + 1)


def test_closed_batches_share_one_length_multiset():
    mix = _tiny("proteins_swissprot", docs_per_call=32, pool_calls=3)
    want = sorted(gen.length_quantiles(mix, 32).tolist())
    for seed in (1, BIG_SEED):
        for batch in _make(mix, seed):
            assert sorted(map(len, batch)) == want
            assert set(b"".join(batch)) <= set(gen.RESIDUES)


def test_length_quantiles_stay_in_range():
    mix = registry.traffic("logs_16k_64k")
    q = gen.length_quantiles(mix, 64)
    assert q.min() >= mix["min_bytes"] and q.max() <= mix["max_bytes"]
    assert np.all(np.diff(q) >= 0)


def test_stream_arrivals_repeat_and_cut_flows_in_order():
    mix = _tiny("ids_flows", flows=8, rate_per_s=200)
    a = _make(mix, BIG_SEED)
    b = _make(mix, BIG_SEED)
    assert np.array_equal(a.due, b.due) and a.flows == b.flows
    assert np.all(np.diff(a.due) >= 0) and a.due.max() < 1.0
    lens = a.end - a.start
    assert lens.min() >= mix["seg_min"] and lens.max() <= mix["seg_max"]
    for f in range(8):
        segs = [a.segment(i) for i in range(a.n) if a.flow[i] == f]
        assert b"".join(segs) == a.flows[f]
    c = _make(mix, BIG_SEED + 1)
    assert c.flows != a.flows
