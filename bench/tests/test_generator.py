"""The traffic generator: determinism by seed, and the drawn lengths."""
import json
from pathlib import Path

import numpy as np
import pytest

import generator as G
import harness

BENCH = Path(__file__).resolve().parents[1]
BIG_SEED = 2**33 + 12345          # seeds wider than 32 bits


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def closed():
    return harness.load_kind(BENCH, {"kind": "closed"})


def test_lognormal_lengths_median_and_clip():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.7, "lo": 32,
         "hi": 1024}
    q = G.quantiles(d, 1001)
    assert np.median(q) == 256
    assert q.min() >= d["lo"] and q.max() <= d["hi"]
    inner = q[(q > d["lo"]) & (q < d["hi"])]
    assert np.std(np.log(inner)) == pytest.approx(d["sigma"], rel=0.15)


def test_loguniform_lengths():
    q = G.quantiles({"dist": "loguniform", "lo": 512, "hi": 2048}, 2000)
    assert q.min() >= 512 and q.max() <= 2048
    assert np.median(q) == pytest.approx(1024, rel=0.01)


def test_closed_loop_blocks_share_their_lengths():
    m = mix("reason-long")
    stream = closed().Stream(m, BIG_SEED, 1000)
    first = [stream.next(u) for u in range(m["users"])]
    second = [stream.next(u) for u in range(m["users"])]
    want = sorted(G.quantiles(m["prompt_len"], m["users"]))
    assert sorted(len(s.prompt) for s in first) == want
    assert sorted(len(s.prompt) for s in second) == want
    again = closed().Stream(m, BIG_SEED, 1000)
    assert [again.next(u).prompt.tolist() for u in range(m["users"])] == \
        [s.prompt.tolist() for s in first]
    # every seed serves the same sessions; the seed only orders them
    orders = set()
    for delta in range(1, 6):
        other = closed().Stream(m, BIG_SEED + delta * 2**32, 1000)
        got = [other.next(u) for u in range(m["users"])]
        assert sorted((s.prompt.tolist(), s.max_new) for s in got) == \
            sorted((s.prompt.tolist(), s.max_new) for s in first)
        orders.add(tuple(len(s.prompt) for s in got))
    assert len(orders) > 1


def test_reason_long_fits_its_pool():
    """Every session's worst case fits the slot's share of the pinned pool,
    so the sessions are admitted at once and none waits for blocks."""
    conf = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    m, srv = mix("reason-long"), conf["serving"]
    worst = closed().max_context(m) + srv["gamma"] + 1
    blocks = -(-worst // srv["block_size"])
    assert m["users"] * blocks <= srv["kv_pool_blocks"] - 1
    assert worst <= conf["hf_config"]["max_position_embeddings"]
