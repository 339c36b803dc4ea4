"""The seeded traffic generator: repeatable per seed, the same set of
lengths and gaps for every seed (in the seed's order where the traffic
shuffles), a pre-roll before the window, lengths within their laws."""
import numpy as np

import bench_smoke  # noqa: F401
import harness
import traffic

CHAT = {"shape_seed": 7, "arrivals": {"kind": "poisson", "rate": 5.0},
        "prompt": {"law": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 32, "max": 2048},
        "output": {"law": "lognormal", "median": 32, "sigma": 0.5,
                   "min": 16, "max": 64}}


def _key(items):
    return [(it.arrival, it.max_new, it.prompt.tobytes()) for it in items]


def _shape(items):
    return [(i.arrival, len(i.prompt), i.max_new) for i in items]


def test_same_seed_same_requests():
    a = traffic.make(CHAT, 2**40 + 3, 10.0, 1000)
    b = traffic.make(CHAT, 2**40 + 3, 10.0, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.make(CHAT, 2**40 + 4, 10.0, 1000))


def test_without_shuffle_every_seed_offers_the_same_schedule():
    a = traffic.make(CHAT, 1, 10.0, 1000)
    b = traffic.make(CHAT, 2, 10.0, 1000)
    assert len(a) == len(b) == 50
    assert _shape(a) == _shape(b)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = dict(CHAT, shape_seed=8)
    assert [i.arrival for i in traffic.make(other, 1, 10.0, 1000)] != [
        i.arrival for i in a]


def test_shuffle_reorders_the_same_lengths_and_gaps():
    spec = dict(CHAT, shuffle=True)
    a = traffic.make(spec, 1, 10.0, 1000)
    b = traffic.make(spec, 2, 10.0, 1000)
    assert _shape(a) != _shape(b)
    assert sorted((len(i.prompt), i.max_new) for i in a) == sorted(
        (len(i.prompt), i.max_new) for i in b)
    gaps = [np.sort(np.diff([i.arrival for i in x])) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)
    assert _shape(a) == _shape(traffic.make(spec, 1, 10.0, 1000))


def test_preroll_arrives_before_the_window():
    spec = dict(CHAT, preroll_seconds=4.0)
    items = traffic.make(spec, 5, 10.0, 1000)
    arr = np.asarray([i.arrival for i in items])
    assert len(items) == traffic.count(spec, 10.0) == 70
    assert arr[0] == -4.0 and arr[-1] < 10.0 and (arr < 0).sum() == 20
    # shuffling moves no request between the pre-roll and the window
    mixed = traffic.make(dict(spec, shuffle=True), 6, 10.0, 1000)
    win = sorted((i.max_new, len(i.prompt)) for i in items if i.arrival >= 0)
    assert win == sorted((i.max_new, len(i.prompt)) for i in mixed
                         if i.arrival >= 0)


def test_arrivals_and_lengths_stay_in_range():
    items = traffic.make(CHAT, 5, 10.0, 1000)
    arr = [i.arrival for i in items]
    assert arr == sorted(arr) and arr[0] == 0.0 and arr[-1] < 10.0
    assert all(32 <= len(i.prompt) <= 2048 for i in items)
    assert all(16 <= i.max_new <= 64 for i in items)
    assert all(i.prompt.dtype == np.int32 and i.prompt.max() < 1000
               for i in items)
    assert traffic.length_range(CHAT) == (32, 2048, 16, 64)


def test_backlog_is_due_at_once():
    spec = dict(CHAT, arrivals={"kind": "backlog", "n": 12})
    items = traffic.make(spec, 9, 30.0, 100)
    assert len(items) == 12 and all(i.arrival == 0.0 for i in items)


def test_cells_traffic_is_heavy_tailed():
    spec = harness.load_json("workloads", "glm6b-w4-chat")["traffic"]
    items = traffic.make(spec, 3, 30.0, 65024)
    for lens in ([len(i.prompt) for i in items], [i.max_new for i in items]):
        assert np.median(lens) < np.mean(lens) < np.percentile(lens, 90)
