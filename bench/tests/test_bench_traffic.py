"""The traffic generator serves every seed the same work, in the seed's own
order, and the open loop releases requests on the wall clock."""
import dataclasses
import json
import time
from collections import Counter

import numpy as np
import pytest

from bench import run as R
from bench import traffic
from bench.tests import tiny


def _by_phase(mix, items, seconds):
    out = []
    for a, b, n in traffic.phases(mix, seconds):
        ph = [x for x in items if a <= x.due < b]
        assert len(ph) == n
        out.append(ph)
    return out


def test_same_seed_same_requests_other_seed_same_schedule():
    mix = traffic.load("chat")
    a = traffic.generate(mix, 2**40 + 7, 1000, 51.0)
    b = traffic.generate(mix, 2**40 + 7, 1000, 51.0)
    c = traffic.generate(mix, 12, 1000, 51.0)
    assert [(x.due, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new, x.prompt.tolist()) for x in b]
    # another seed: in each phase (pre-roll, window, after) the same count
    # of requests and the same lengths, in another order on another
    # schedule, with other tokens
    for pa, pc in zip(_by_phase(mix, a, 51.0), _by_phase(mix, c, 51.0)):
        assert Counter((len(x.prompt), x.max_new) for x in pa) == \
            Counter((len(x.prompt), x.max_new) for x in pc)
    assert [x.due for x in a] != [x.due for x in c]
    assert [x.max_new for x in a] != [x.max_new for x in c]
    assert a[0].prompt.tolist() != c[0].prompt.tolist()
    due = np.array([x.due for x in a])
    assert np.all(np.diff(due) > 0) and due[0] >= -mix["preroll_s"]
    window = _by_phase(mix, a, 51.0)[1]
    assert len(window) == round(mix["rate_per_s"] * 51.0)
    many = traffic.generate({**mix, "rate_per_s": 4.0}, 3, 1000, 200.0)
    lens = np.array([len(x.prompt) for x in many])
    assert lens.min() >= 32 and lens.max() <= 2048
    assert 200 <= np.median(lens) <= 320


def test_poisson_counts_and_bursts():
    mix = {**traffic.load("chat"), "rate_per_s": 2.0}
    items = traffic.generate(mix, 5, 1000, 500.0)
    window = _by_phase(mix, items, 500.0)[1]
    gaps = np.diff([x.due for x in window])
    # Poisson given its count: exponential gaps, coefficient of variation 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15
    burst = traffic.generate({**mix, "burst_cv": 3.0}, 5, 1000, 500.0)
    window = _by_phase(mix, burst, 500.0)[1]
    gaps = np.diff([x.due for x in window])
    assert gaps.std() / gaps.mean() > 2.0


def test_shared_prefix_and_seeded_backlog():
    mix = {**traffic.load("chat"), "shared_prefix": {
        "groups": 2, "len": {"uniform": [16, 16]}}}
    mix["prompt"] = {"uniform": [24, 40]}
    items = traffic.generate(mix, 8, 1000, 20.0)
    heads = {tuple(x.prompt[:16].tolist()) for x in items}
    assert len(heads) == 2
    bmix = {**traffic.load("batch"), "order": "seeded"}
    a = traffic.generate(bmix, 1, 1000, 20.0)
    b = traffic.generate(bmix, 2, 1000, 20.0)
    assert [x.max_new for x in a] != [x.max_new for x in b]
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)


def test_generator_named_by_the_mix_is_loaded(tmp_path):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "steady.py").write_text(
        "import numpy as np\n"
        "from bench.traffic import Item\n"
        "def generate(mix, seed, vocab, seconds):\n"
        "    return [Item(np.full(mix['n'], seed % vocab, np.int32), 4, None)]\n")
    (tmp_path / "traffic" / "mine.json").write_text(json.dumps(
        {"generator": "steady", "n": 3, "backlog": 1, "preroll_s": 0}))
    mix = traffic.load("mine", tmp_path)
    (item,) = traffic.generate(mix, 7, 5, 1.0)
    assert item.prompt.tolist() == [2, 2, 2] and item.due is None


def test_backlog_first_batch_is_staggered():
    mix = traffic.load("batch")
    a = traffic.generate(mix, 5, 1000, 20.0)
    assert len(a) == mix["pool"] and all(x.due is None for x in a)
    first = [x.max_new for x in a[:mix["backlog"]]]
    assert min(first) < 256 and all(256 <= x.max_new <= 768
                                    for x in a[mix["backlog"]:])
    assert traffic.longest(mix) == 1024 + 768


class SlowEngine:
    """Stands in for the engine: every step takes 40 ms and finishes one
    token of each of up to two requests."""

    class Req:
        def __init__(self, n, cb):
            self.n, self.cb, self.out_tokens, self.rid = n, cb, [], id(self)

        @property
        def done(self):
            return len(self.out_tokens) >= self.n

        prefilling = False

        @property
        def fed(self):
            return len(self.out_tokens)

    class Cfg:
        num_slots, prefill_chunk = 2, 8

    def __init__(self):
        self.queue, self.slots, self.ecfg = [], [None, None], self.Cfg()

    @property
    def busy(self):
        return bool(self.queue) or any(self.slots)

    def submit(self, prompt, n, on_token=None):
        r = self.Req(n, on_token)
        self.queue.append(r)
        return r

    def step(self):
        time.sleep(0.04)
        for i in range(2):
            if self.slots[i] is None and self.queue:
                self.slots[i] = self.queue.pop(0)
        done = []
        for i, r in enumerate(self.slots):
            if r is not None:
                r.out_tokens.append(1)
                r.cb(r, 1)
                if r.done:
                    done.append(r)
                    self.slots[i] = None
        return done


def test_open_loop_releases_on_the_wall_clock_and_times_from_due():
    mix = {**tiny.mix(), "rate_per_s": 50.0, "preroll_s": 0.0,
           "grace_s": 5.0}
    cell = dataclasses.replace(tiny.cell(), mix=mix)
    items = traffic.generate(mix, 1, 100, 0.5)
    for it in items:
        it.max_new = 3
    rec, tracks, open_t, close_t, _, late = R.drive(SlowEngine(), cell,
                                                    items, 0.5)
    due = [t for t in tracks if open_t <= t.due < close_t]
    assert len(due) >= 10
    # each request is released within one step of its due time, whatever
    # the queue holds, and its due time is the schedule's
    assert max(late) < 0.1
    for t, it in zip(tracks, items):
        assert t.due == open_t + it.due
    # 50 requests/s against 2 x 25 tokens/s of service: the queue grows, so
    # the wait shows in time-to-first-token measured from the due time
    ttft = [t.times[0] - t.due for t in due]
    assert ttft[-1] > ttft[0] + 0.1


def test_backlog_preroll_is_counted_in_steps():
    mix = {"kind": "backlog", "backlog": 2, "preroll_steps": 5}
    cell = dataclasses.replace(tiny.cell(), mix=mix)
    items = [traffic.Item(np.zeros(3, np.int32), 3, None) for _ in range(4)]
    rec, tracks, open_t, close_t, _, _ = R.drive(SlowEngine(), cell, items,
                                                 0.3)
    before = [s for s in rec.steps if s.t0 < open_t]
    assert len(before) == 5 and before[-1].t1 <= open_t
    # the queue is topped up from the pool, round again, through the window
    assert len(tracks) > len(items)
    assert all(t.submitted < close_t for t in tracks)
    assert close_t - open_t == pytest.approx(0.3)
