"""The load generator and the window's loop, on the CPU without a model."""

import sys
from collections import deque
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import serving, spec, traffic  # noqa: E402

BATCH = spec._read_json(ROOT / "bench/traffic/batch.json")
BURST = spec._read_json(ROOT / "bench/traffic/burst.json")


def _lengths(reqs):
    return [(len(r.prompt), r.max_new) for r in reqs]


@pytest.mark.parametrize("mix", [BATCH, BURST], ids=["batch", "burst"])
def test_same_seed_same_requests(mix):
    seed = 2 ** 40 + 123  # wider than 32 bits
    a = traffic.make_requests(mix, 32000, seed, 40)
    b = traffic.make_requests(mix, 32000, seed, 40)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due) == (y.max_new, y.due)
    c = traffic.make_requests(mix, 32000, seed + 2 ** 32, 40)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", [dict(BATCH, schedule_seed=None),
                                 dict(BURST, schedule_seed=None)],
                         ids=["batch", "burst"])
def test_seeds_share_sizes_per_block(mix):
    """Every seed sends the same lengths (and gaps) in each block."""
    mix = {k: v for k, v in mix.items() if v is not None}
    block = mix["block"]
    a = traffic.make_requests(mix, 1000, 1, 40)
    b = traffic.make_requests(mix, 1000, 2, 40)
    assert _lengths(a) != _lengths(b)
    for i in range(0, len(a), block):
        for j in (0, 1):
            assert sorted(x[j] for x in _lengths(a[i:i + block])) == sorted(
                x[j] for x in _lengths(b[i:i + block]))
    for r in a:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000


def test_schedule_seed_replays_one_schedule():
    """With a schedule seed, seeds change the token ids only."""
    a = traffic.make_requests(BURST, 32000, 1, 51)
    b = traffic.make_requests(BURST, 32000, 2 ** 33 + 2, 51)
    assert _lengths(a) == _lengths(b)
    assert [r.due for r in a] == [r.due for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lognormal_median():
    q = traffic.quantiles(32)
    x = traffic.lengths({"dist": "lognormal", "median": 96, "sigma": 0.6,
                         "min": 1, "max": 10 ** 6}, q)
    assert np.median(x) == pytest.approx(96, abs=2)


def test_gamma_rate_and_burstiness():
    mix = dict(BURST, arrivals={"dist": "gamma", "shape": 0.25, "rate": 5.0})
    reqs = traffic.make_requests(mix, 1000, 7, 60)
    dues = np.array([r.due for r in reqs])
    assert dues[0] == 0.0 and np.all(np.diff(dues) >= 0)
    gaps = np.diff(np.append(dues, dues[-1]))
    block = mix["block"]
    # each block's arrivals span exactly block / rate seconds
    spans = [dues[i + block] - dues[i] for i in range(0, len(dues) - block,
                                                      block)]
    assert spans == pytest.approx([block / 5.0] * len(spans))
    assert len(reqs) >= 2 * 5.0 * 60
    # shape 0.25: coefficient of variation near 2 (bursts)
    cv = np.std(gaps[:-1]) / np.mean(gaps[:-1])
    assert 1.3 < cv < 2.2


class _FakeEngine:
    """Slots, a queue and chunked decode, as ServingEngine's host loop."""

    def __init__(self, max_batch=8, chunk=8):
        self.queue, self.slots, self.chunk = deque(), [None] * max_batch, chunk
        self.concurrency = []

    def submit(self, req):
        req.output = []
        self.queue.append(req)

    def step(self):
        for i, s in enumerate(self.slots):
            if s is None and self.queue:
                self.slots[i] = self.queue.popleft()
        live = [r for r in self.slots if r is not None]
        self.concurrency.append(len(live))
        for r in live:
            r.output += [1] * min(self.chunk, r.max_new_tokens - len(r.output))
        for i, r in enumerate(self.slots):
            if r is not None and len(r.output) == r.max_new_tokens:
                r.done, self.slots[i] = True, None
        return True


def test_closed_loop_keeps_every_client_busy():
    mix = dict(BATCH, clients=8)
    cell = spec.Cell("c", 1, {}, mix, {}, [], [], ROOT)
    eng = _FakeEngine()
    reqs = traffic.make_requests(mix, 100, 3, 1)
    loop, t0, t_end, _, _ = serving.drive(eng, cell, reqs, 0.02)
    assert eng.concurrency and set(eng.concurrency) == {8}
    assert len(loop.tracks) > 8
    done = [t for t in loop.tracks.values() if t.done_t is not None]
    assert all(len(t.req.output) == t.spec.max_new for t in done)
    assert t_end >= t0 + 0.02
    # the chunks count exactly the tokens the syncs returned in the window
    emitted = sum(a - b for c in loop.chunks for _, _, b, a in c.emitted)

    def in_window(t):
        before = max([n for s, n in t.syncs if s <= t0], default=0)
        return (t.syncs[-1][1] if t.syncs else 0) - before

    assert emitted == sum(in_window(t) for t in loop.tracks.values()) > 0


def test_open_loop_submits_on_schedule():
    mix = dict(BURST, arrivals={"dist": "gamma", "shape": 0.25,
                                "rate": 50.0})
    cell = spec.Cell("c", 1, {}, mix, {}, [], [], ROOT)
    reqs = traffic.make_requests(mix, 100, 4, 0.5)
    loop, t0, t_end, _, _ = serving.drive(_FakeEngine(), cell, reqs, 0.5)
    due = [t.due - t0 for t in loop.tracks.values()]
    assert due == pytest.approx([r.due for r in reqs[:len(due)]])
    assert max(due) <= t_end - t0
    assert len(due) >= sum(1 for r in reqs if r.due < 0.45)
