"""The open-loop generator: deterministic per seed, the file's rate and
bands, and the same work for every seed."""
import json
import os

import numpy as np

from bench.traffic.openloop import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def chat():
    with open(os.path.join(TRAFFIC, "serve.chat.json")) as f:
        return json.load(f)


def test_deterministic_per_seed():
    t = chat()
    a = schedule(t, 2**33 + 5, 30.0, 151936)
    b = schedule(t, 2**33 + 5, 30.0, 151936)
    c = schedule(t, 2**33 + 6, 30.0, 151936)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_rate_and_bands():
    t = chat()
    s = schedule(t, 7, 60.0, 151936)
    due = np.array([x.due_s for x in s])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 60.0
    assert abs(len(s) / 60.0 - t["rate_per_s"]) / t["rate_per_s"] < 0.02
    assert abs(60.0 / len(s) - np.mean(np.diff(due))) / (60.0 / len(s)) < 0.1
    lo, hi = t["prompt_len"]
    plen = np.array([len(x.prompt) for x in s])
    assert plen.min() >= lo and plen.max() <= hi
    # log-uniform: the median sits near the geometric mean of the band
    assert abs(np.log(np.median(plen)) - 0.5 * np.log(lo * hi)) < 0.1
    olo, ohi = t["output_len"]
    olen = np.array([x.output_len for x in s])
    assert olen.min() >= olo and olen.max() <= ohi
    assert all(x.prompt.max() < 151936 for x in s)


def test_every_seed_offers_the_same_work():
    """Position by position: the same due time and lengths, other ids."""
    t = chat()
    a = schedule(t, 1, 30.0, 1000)
    b = schedule(t, 2**33 + 2, 30.0, 1000)
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.output_len for x in a] == [x.output_len for x in b]
    # the warm-up and the window are separate schedules
    w = schedule(t, 1, 30.0, 1000, stream=1)
    assert [len(x.prompt) for x in w] != [len(x.prompt) for x in a]


def test_check_sample_holds_min_requests():
    """The longest request alone passes min_tokens; the sample still holds
    min_requests requests, the others drawn from the seed."""
    from types import SimpleNamespace

    from bench.kinds.serve import Recorder, pick_sample
    rec, finished = Recorder(), {}
    for i in range(20):
        rid, n = f"req-{i}", (500 if i == 7 else 10 + i)
        rec.prompt[rid] = np.arange(5)
        rec.tokens[rid] = [(j, j, 0.0) for j in range(n)]
        finished[rid] = SimpleNamespace(generated=list(range(n)))
    check = {"min_requests": 8, "min_tokens": 400, "max_requests": 12}
    a = [len(s) for _, s in pick_sample(finished, rec, check, 5)]
    assert len(a) == 8 and a[0] == 500
    assert a == [len(s) for _, s in pick_sample(finished, rec, check, 5)]
    assert a != [len(s) for _, s in pick_sample(finished, rec, check, 6)]
