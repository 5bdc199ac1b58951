"""The trace reduction on a trace recorded on one TPU v5e.

``data/bitnet_batch.xplane.pb.gz`` is 0.6 s of a ``--trace 1`` run of
``bitnet3b-batch`` (``jax.profiler``, host annotations only). The reduction
is checked against a second, independent count over the same events and
against numbers read off the trace by hand.
"""

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "bitnet_batch.xplane.pb.gz"


@pytest.fixture(scope="module")
def pd():
    return trace.load(gzip.decompress(DATA.read_bytes()))


@pytest.fixture(scope="module")
def reduced(pd):
    return trace.reduce(pd)


def _events(pd, plane_prefix, line_name):
    return [(e.name, float(e.start_ns), float(e.start_ns) + e.duration_ns)
            for p in pd.planes if p.name.startswith(plane_prefix)
            for line in p.lines if line.name == line_name
            for e in line.events]


def test_union_by_an_independent_sweep(pd, reduced):
    a, b = reduced["window_ns"]
    marks = []
    for _, s, e in _events(pd, "/device:", trace.OPS_LINE):
        s, e = max(s, a), min(e, b)
        if e > s:
            marks += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(marks, key=lambda m: (m[0], -m[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    assert reduced["busy_s"] == pytest.approx(busy / 1e9, rel=1e-12)
    assert reduced["window_s"] == pytest.approx((b - a) / 1e9)
    assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_programs_and_breakdown(pd, reduced):
    decode = reduced["programs"]["jit__decode_chunk_impl"]
    a, b = reduced["window_ns"]
    want = sum(min(e, b) - max(s, a) for n, s, e in
               _events(pd, "/device:", trace.MODULES_LINE)
               if n.startswith("jit__decode_chunk_impl") and e > a and s < b)
    assert decode["seconds"] == pytest.approx(want / 1e9)
    secs, count, ivs = trace.program_seconds(reduced, "_decode_chunk_impl")
    assert (secs, count) == (decode["seconds"], decode["count"])
    ops = reduced["device_ops"]
    assert 0 < len(ops) <= 10
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = reduced["idle_gaps"]
    assert len(gaps) <= 10
    assert {tag for tag, _ in gaps} <= set(trace.HARNESS_SPANS) | {"none"}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle + 1e-9


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.union([]) == []
