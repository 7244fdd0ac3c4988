"""The traffic generator: deterministic from the seed, inside its clips,
the same work for every seed."""
import json
import os

import numpy as np
import pytest

import pbench_tiny
from pbench import traffic as T


def _mix(name):
    with open(os.path.join(pbench_tiny.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def _server(traffic):
    """The server settings of the configuration the mix runs on."""
    from pbench import manifest
    m = manifest.load_manifest(pbench_tiny.REPO)
    w = next(w for w in m["workloads"] if w["traffic"] == traffic)
    conf = next(c for c in m["configs"] if c["name"] == w["config"])
    with open(os.path.join(pbench_tiny.REPO, conf["file"])) as f:
        return json.load(f)["server"]


SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("name", ["chat", "code"])
def test_same_seed_same_inputs(name):
    mix = _mix(name)
    make = ((lambda s: T.open_loop(mix, s, 30, 1000)) if mix["loop"] == "open"
            else (lambda s: T.closed_loop(mix, s, 1000)))
    a, b, c = make(SEED), make(SEED), make(SEED + 1)
    key = lambda arr: [(x.offset, x.length, x.tokens.tolist()) for x in arr]
    assert key(a) == key(b)
    assert key(a) != key(c)
    # another seed: the same lengths and gaps in another order
    assert sorted(x.length for x in a) == sorted(x.length for x in c)


@pytest.mark.parametrize("name", ["chat", "code"])
def test_lengths_within_clips_and_tokens_in_vocab(name):
    mix = _mix(name)
    p = mix["prompt"]
    arr = (T.open_loop(mix, SEED, 30, 997) if mix["loop"] == "open"
           else T.closed_loop(mix, SEED, 997))
    lens = np.array([x.length for x in arr])
    assert lens.min() >= p["min"] and lens.max() <= p["max"]
    assert all(len(x.tokens) == x.length for x in arr)
    assert all(x.tokens.min() >= 0 and x.tokens.max() < 997 for x in arr)
    assert abs(np.median(lens) - p["median"]) <= 0.05 * p["median"]
    # a prompt long enough to reach max_seq would be refused
    server = _server(name)
    assert lens.max() + server["max_new_tokens"] < server["max_seq"]


def test_open_loop_window_holds_rate_times_seconds():
    mix = _mix("chat")
    for seed in (1, SEED):
        arr = T.open_loop(mix, seed, 30, 100)
        counted = [x for x in arr if x.counted]
        assert len(counted) == round(mix["rate_rps"] * 30)
        assert all(0 <= x.offset < 30 for x in counted)
        lead = [x.offset for x in arr if x.offset < 0]
        assert lead and min(lead) >= -mix["lead_s"]
        offs = [x.offset for x in arr]
        assert offs == sorted(offs)


def test_open_loop_times_requests_from_their_scheduled_arrival():
    """Each request the run submits is stamped with its scheduled arrival,
    so a stall counts against the requests behind it; its first and later
    tokens are stamped by the benchmark when the call that emitted them
    returns."""
    import tempfile

    from pbench import harness, manifest, serving
    with tempfile.TemporaryDirectory() as root:
        pbench_tiny.make(root)
        cell = manifest.load_cell("minicpm_2b.chat", root)
        server = serving.build_server(cell, 3)
        rec = serving.Recorder()
        rec.wrap(server)
        out = serving.run(server, rec, cell.traffic, 3, 1.0)
    arr = {a.rid: a for a in T.open_loop(cell.traffic, 3, 1.0, 512)}
    ws = out.window[0]
    assert out.counted and not out.failed
    for r in out.counted:
        assert rec.arrival[r.rid] == pytest.approx(ws + arr[r.rid].offset,
                                                   abs=1e-9)
    for r in out.finished:
        assert rec.emitted[r.rid] == len(r.output)
        assert rec.ttft_s(r.rid) == rec.first[r.rid] - rec.arrival[r.rid]
        assert rec.first[r.rid] <= rec.last[r.rid]
        calls = [c.t1 for c in rec.calls]
        assert rec.first[r.rid] in calls and rec.last[r.rid] in calls
    assert harness  # the harness imports without a chip


def test_percentile_is_linear_between_order_statistics():
    assert T.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == \
        pytest.approx(9.1)
