"""The generator: a seed repeats exactly; seeds differ only in order."""
import json

import numpy as np

from bench.core import BENCH
from bench.traffic.gen import Traffic


def _mix(name):
    return json.load(open(BENCH / "traffic" / f"{name}.json"))


def _draw(mix, seed, n=200):
    t = Traffic(mix, seed)
    return [t.size(t.next_index()) for _ in range(n)]


def test_same_seed_repeats_exactly():
    mix = _mix("chat_open")
    big = 2 ** 31 + 12345
    assert _draw(mix, big) == _draw(mix, big)
    assert Traffic(mix, big).due_times(51.0) == Traffic(mix, big).due_times(
        51.0)


def test_seeds_share_the_work_in_another_order():
    mix = _mix("chat_open")
    a, b = Traffic(mix, 1), Traffic(mix, 2)
    n = a.n
    for key in ("prompt", "output"):
        sa = sorted(a.size(i)[key] for i in range(n))
        sb = sorted(b.size(i)[key] for i in range(n))
        assert sa == sb
    assert [a.size(i) for i in range(20)] != [b.size(i) for i in range(20)]
    # one pool of arrivals: the same total span, in another order
    assert np.isclose(a.due[-1], b.due[-1])


def test_open_loop_rate_and_bounds():
    mix = _mix("chat_open")
    t = Traffic(mix, 7)
    due = t.due_times(51.0)
    assert abs(len(due) / 51.0 - mix["rate"]) < 0.15 * mix["rate"]
    sizes = [t.size(i) for i in range(t.n)]
    p = mix["sizes"]["prompt"]
    assert min(s["prompt"] for s in sizes) >= p["lo"]
    assert max(s["prompt"] for s in sizes) <= p["hi"]
    med = float(np.median([s["prompt"] for s in sizes]))
    assert abs(med - p["median"]) < 0.05 * p["median"]


def test_closed_loop_sizes():
    t = Traffic(_mix("longdoc_backlog"), 3)
    s = [t.size(i) for i in range(t.n)]
    assert all(8192 <= x["prompt"] <= 16384 for x in s)
    assert all(32 <= x["output"] <= 128 for x in s)
    assert [x["steps"] for x in _draw(_mix("denoise_backlog"), 5, 10)] \
        == [4] * 10
