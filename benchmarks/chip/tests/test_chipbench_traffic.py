"""The one traffic generator: deterministic under the seed, and true to
each mix's stated lengths, rates and sharing."""

import json

import numpy as np
import pytest

from chipbench_testlib import BENCH, DATA
from chipbench import traffic


def mix(name):
    """A mix of the benchmark's, or a test's own (tests/data)."""
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        path = DATA / f"{name}.json"
    return json.loads(path.read_text())


def items(m, seed, horizon=30.0, vocab=49152):
    return traffic.Source(m, seed, vocab=vocab, horizon_s=horizon).initial()


@pytest.mark.parametrize("name", ["chat", "tiny-batch", "sessions"])
def test_same_seed_same_requests(name):
    a, b = items(mix(name), 2**31 + 12345), items(mix(name), 2**31 + 12345)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat", "tiny-batch", "sessions"])
def test_other_seed_other_tokens(name):
    a, b = items(mix(name), 1), items(mix(name), 2)
    assert any(not np.array_equal(x.prompt[:8], y.prompt[:8])
               for x, y in zip(a, b))


def test_seeds_share_the_sizes_and_gaps():
    """Every seed offers the same lengths, outputs and arrival gaps, each
    seed in an order of its own."""
    m = mix("chat")
    a, b = [traffic.Source(m, s, vocab=100, horizon_s=60.0) for s in (3, 4)]
    n = len(a.plens)
    u = (np.arange(n) + 0.5) / n
    first = -np.mean(-np.log1p(-u) / m["rate_per_s"])    # due0 - the first gap

    def gaps(src):
        return np.diff(src.due0, prepend=first)

    for x, y in ((a.plens, b.plens), (a.olens, b.olens), (gaps(a), gaps(b))):
        np.testing.assert_allclose(np.sort(x), np.sort(y))
        assert not np.array_equal(x, y)


def test_reorder_swaps_only_values_adjacent_in_size():
    """Each value keeps its place in the fixed order up to a swap with a
    value of its own group of `GROUP` ranks; the seed picks the swaps."""
    x = np.arange(41.0)
    one = traffic.reorder(x.copy(), 3, 2**40 + 1)
    np.testing.assert_array_equal(one, traffic.reorder(x.copy(), 3, 2**40 + 1))
    other = traffic.reorder(x.copy(), 3, 7)
    fixed = x[traffic.seed_rng(traffic.ORDER, 3).permutation(41)]
    for got in (one, other):
        np.testing.assert_array_equal(got // traffic.GROUP,
                                      fixed // traffic.GROUP)
    assert not np.array_equal(one, other)


def test_chat_lengths_and_rate_meet_the_mix():
    m = mix("chat")
    src = traffic.Source(m, 9, vocab=100, horizon_s=200.0)
    reqs = src.initial()
    plens = np.array([len(r.prompt) for r in reqs])
    olens = np.array([r.max_new for r in reqs])
    assert plens.min() >= m["prompt"]["min"]
    assert plens.max() <= m["prompt"]["max"]
    assert olens.min() >= m["output"]["min"]
    assert olens.max() <= m["output"]["max"]
    # truncation trims both tails a little; the median stays near its own
    assert abs(np.median(plens) / m["prompt"]["median"] - 1) < 0.08
    assert abs(np.median(olens) / m["output"]["median"] - 1) < 0.08
    due = np.array([r.due for r in reqs])
    assert np.all(np.diff(due) >= 0)
    rate = len(due) / (due[-1] - due[0])
    assert abs(rate / m["rate_per_s"] - 1) < 0.05


def test_closed_loop_keeps_its_clients_busy():
    m = mix("tiny-batch")
    src = traffic.Source(m, 5, vocab=100, horizon_s=0.0)
    first = src.initial()
    assert len(first) == m["clients"] and all(r.due == 0 for r in first)
    nxt = src.on_done(first[0], [1, 2, 3], 7.5)
    assert len(nxt) == 1 and nxt[0].due == 7.5
    assert m["prompt"]["min"] <= len(nxt[0].prompt) <= m["prompt"]["max"]


def test_open_loop_sends_no_follow_up():
    src = traffic.Source(mix("chat"), 5, vocab=100, horizon_s=10.0)
    assert src.on_done(src.initial()[0], [1], 1.0) == []


def test_sessions_share_system_prompts_and_resend_history():
    m = mix("sessions")
    s = m["session"]
    src = traffic.Source(m, 11, vocab=1000, horizon_s=200.0)
    first = src.initial()
    systems = {tuple(p) for p in src.system}
    assert len(src.system) == s["system_pool"]
    assert all(s["system_len"]["min"] <= len(p) <= s["system_len"]["max"]
               for p in src.system)
    used = set()
    for it in first:
        head = [p for p in systems if tuple(it.prompt[:len(p)]) == p]
        assert head, "a session's first turn starts with a pooled prompt"
        used.add(head[0])
        user = len(it.prompt) - len(head[0])
        assert s["user_len"]["min"] <= user <= s["user_len"]["max"]
        assert s["reply_len"]["min"] <= it.max_new <= s["reply_len"]["max"]
        assert s["think_s"]["min"] <= it.think_s <= s["think_s"]["max"]
    assert len(used) == s["system_pool"]
    # a follow-up turn is the whole history plus the next user message
    turns = {}
    it = first[0]
    while True:
        reply = list(range(it.max_new))
        nxt = src.on_done(it, reply, 100.0)
        if not nxt:
            break
        (nx,) = nxt
        hist = np.concatenate([it.prompt, np.asarray(reply, np.int32)])
        np.testing.assert_array_equal(nx.prompt[:len(hist)], hist)
        assert nx.due == pytest.approx(100.0 + it.think_s)
        assert len(nx.prompt) + nx.max_new <= s["max_context"]
        turns[nx.turn] = nx
        it = nx
    assert len(turns) + 1 <= s["turns"]["max"]


def test_session_turn_counts_cover_the_range():
    m = mix("sessions")
    src = traffic.Source(m, 4, vocab=100, horizon_s=300.0)
    got = set(int(t) for t in src.n_turns)
    assert got == set(range(m["session"]["turns"]["min"],
                            m["session"]["turns"]["max"] + 1))
