"""The traffic generator: the same seed gives the same inputs, every seed
gets the same schedule with token ids of its own, and the sets hit their
distributions."""

import numpy as np
import pytest

from benchmark import traffic_gen as tg

CHAT = tg.load("chat-lognormal")
SHORT = tg.load("short-uniform")


def test_deterministic_in_the_seed():
    a = tg.serve_schedule(CHAT, 3000000007, 30, 50257)
    b = tg.serve_schedule(CHAT, 3000000007, 30, 50257)
    c = tg.serve_schedule(CHAT, 5, 30, 50257)
    assert a == b and a["prompts"] != c["prompts"]


@pytest.mark.parametrize("traffic", [CHAT, SHORT])
def test_same_schedule_for_every_seed(traffic):
    a = tg.serve_schedule(traffic, 1, 30, 50257)
    b = tg.serve_schedule(traffic, 2, 30, 50257)
    assert a["due"] == b["due"] and a["max_new"] == b["max_new"]
    assert list(map(len, a["prompts"])) == list(map(len, b["prompts"]))
    assert a["prompts"] != b["prompts"]  # the token ids are the seed's
    lengths = list(map(len, a["prompts"]))
    assert lengths != sorted(lengths)    # shuffled, not in quantile order


def test_arrivals_fill_the_span_at_the_rate():
    s = tg.serve_schedule(CHAT, 9, 30, 50257)
    due = np.array(s["due"])
    rate, pre = CHAT["arrivals"]["rate_per_s"], CHAT["preroll_s"]
    assert (due >= 0).sum() == round(rate * 30) and (due < 0).sum() == round(rate * pre)
    assert due[0] == pytest.approx(-pre) and (np.diff(due) >= 0).all()
    assert due[-1] < 30 and due[due >= 0][0] == 0
    gaps = np.diff(due[due >= 0])
    # exponential gaps: the standard deviation is about the mean
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_lengths_hit_their_distributions():
    s = tg.serve_schedule(CHAT, 4, 60, 50257)
    p = np.array(list(map(len, s["prompts"])))
    o = np.array(s["max_new"])
    assert p.min() >= 16 and p.max() <= 512 and o.min() >= 8 and o.max() <= 256
    assert np.median(p) == pytest.approx(160, rel=0.05)
    assert np.median(o) == pytest.approx(64, rel=0.08)
    assert p.mean() > 1.15 * np.median(p)  # a heavy right tail
    u = tg.serve_schedule(SHORT, 4, 10, 50257)
    pu = np.array(list(map(len, u["prompts"])))
    assert pu.min() >= 8 and pu.max() <= 64 and pu.mean() == pytest.approx(36, abs=1.5)
    assert all(0 <= t < 50257 for row in u["prompts"][:50] for t in row)


def test_unknown_processes_and_distributions_are_refused():
    with pytest.raises(ValueError):
        tg.serve_schedule({**CHAT, "arrivals": {"process": "gamma", "rate_per_s": 4.0}},
                          1, 10, 50257)
    with pytest.raises(ValueError):
        tg.quantile_set({"dist": "pareto"}, 10)


def test_host_batches():
    t = tg.load("host-batches-1k-b8")
    a, b = tg.HostBatches(t, 7, 50257), tg.HostBatches(t, 7, 50257)
    x, y = a.next(), b.next()
    assert x["inputs"].shape == (8, 1024) and x["inputs"].dtype == np.int32
    assert (x["inputs"] == y["inputs"]).all() and (x["targets"] == y["targets"]).all()
    assert (x["inputs"][:, 1:] == x["targets"][:, :-1]).all()  # next-token targets
    assert not (a.next()["inputs"] == x["inputs"]).all()       # a fresh batch a step
    # Zipf: a few ids take a large share
    _, counts = np.unique(x["inputs"], return_counts=True)
    assert np.sort(counts)[-10:].sum() > 0.2 * x["inputs"].size


def test_percentile_and_due_time_arithmetic():
    assert tg.percentile([1, 2, 3, 4, 5], 50) == 3
    assert tg.percentile(range(101), 95) == pytest.approx(95)
    assert tg.percentile([10, 20], 95) == pytest.approx(19.5)
    assert tg.percentile([7], 95) == 7
    from benchmark.drivers.serve import summarize

    sched = {"prompts": [[1] * 4] * 4, "max_new": [3] * 4}
    rec = lambda i, due, sent, times, done=True: {
        "i": i, "due": due, "sent": sent, "times": times,
        "tokens": [0] * len(times), "done": done}
    records = [
        rec(0, -1.0, -1.0, [-0.5, 0.5, 1.0]),        # pre-roll: not judged
        rec(1, 1.0, 1.25, [2.0, 2.5, 3.0]),          # sent late: still timed from due
        rec(2, 2.0, 2.0, [2.5, 4.5, 10.5]),          # last token after the window
        rec(3, 3.0, 3.0, [], done=False),            # failed: enters at the timeout
    ]
    out = summarize(records, sched, 10.0, 30.0)
    assert out["attempted"] == 3 and out["failed"] == 1
    # tokens inside [0, 10]: 2 of the pre-roll request, 3, and 2
    e2e = out["end_to_end"]
    assert e2e["serve_tokens_per_s"] == pytest.approx(7 / 10.0)
    assert e2e["ttft_p95_ms"] == pytest.approx(1e3 * tg.percentile([1.0, 0.5, 30.0], 95))
    assert e2e["tpot_p95_ms"] == pytest.approx(1e3 * tg.percentile([0.5, 4.0, 30.0], 95))
    assert out["late_p95_ms"] == pytest.approx(1e3 * tg.percentile([0.25, 0, 0], 95))
    assert out["client_ttft_from_send_p50_s"] == pytest.approx(0.625)
