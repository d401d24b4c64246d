"""The traffic generator: deterministic per seed, the same sizes in
another order across seeds, and every request fits the engine."""
import numpy as np

from perfbench import harness, traffic
from perfbench.tests import tiny

MIXES = [harness.load_json(tiny.BENCH / "mixes" / f"{m}.json")
         for m in ("chat-sessions", "repo-batch")] + [tiny.CHAT, tiny.BATCH]


def _draw(mix, seed, t=60.0):
    g = traffic.make(mix, 1000, seed, t + 10)
    out = g.due_until(t)
    for r in list(out)[:20]:                  # finish a few: next turns
        g.on_finish(r, r.due + 1.0)
    out += g.due_until(t + 30)
    return out


def _sig(reqs):
    return [(r.due, len(r.prompt), r.max_new, r.prompt[:8].tolist())
            for r in reqs]


def test_same_seed_same_traffic():
    for mix in MIXES:
        assert _sig(_draw(mix, 7)) == _sig(_draw(mix, 7))


def test_seeds_differ_in_tokens_only():
    """Another seed draws other token ids for the same sizes and times."""
    for mix in MIXES:
        a, b = _draw(mix, 7), _draw(mix, 2**31 + 5)
        assert [s[:3] for s in _sig(a)] == [s[:3] for s in _sig(b)]
        assert _sig(a) != _sig(b)


def test_every_request_fits_max_seq_len():
    for mix in MIXES:
        for r in _draw(mix, 3):
            assert len(r.prompt) + r.max_new <= mix["engine"]["max_seq_len"]
            assert r.prompt.min() >= 0 and r.prompt.max() < 1000


def test_next_turn_extends_the_previous_prompt():
    g = traffic.make(tiny.CHAT, 300, 4, 200)
    first = [r for r in g.due_until(20.0) if r.turn == 0]
    r = next(r for r in first if g.sizes["turns"][r.session] > 1)
    g.on_finish(r, 100.0)
    nxt = [q for q in g.due_until(150.0) if q.session == r.session]
    assert nxt and nxt[0].turn == 1 and nxt[0].due > 100.0
    assert np.array_equal(nxt[0].prompt[:len(r.prompt)], r.prompt)
    assert len(nxt[0].prompt) > len(r.prompt) + r.max_new
