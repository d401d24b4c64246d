"""One general traffic generator, driven by a mix file's parameters.

Two kinds of mix:

* ``sessions`` — open loop: sessions arrive as a Poisson process.  A
  session opens with one of a few shared system prompts (Zipf popularity)
  and runs a number of turns; the next turn is due a think time after the
  previous answer completed, and its prompt is the previous prompt, a
  stand-in of the answer's length and the new user tokens.  A mix with
  one turn and no system prompt is plain open-loop traffic.
* ``outstanding`` — an offline job: a fixed number of requests is always
  outstanding; each completion is replaced at once.  Requests pick a shared
  context (Zipf popularity) and add a suffix of their own.

Sizes (lengths, turns, think times, arrival times, context choices) are
drawn from the mix's fixed ``shape_seed``; the run's ``--seed`` draws the
token ids.  So every seed offers the same work at the same times, and a
later commit sees the same traffic: a 95th percentile over the few dozen
requests of one window would otherwise move with which long prompts a
seed happens to put in it.  A request whose prompt and output would not fit
``max_seq_len`` ends its session (sizes are fixed, so that is fixed too).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Req:
    """One request as the client sends it."""
    key: int
    prompt: np.ndarray
    max_new: int
    due: float                       # seconds since the traffic's origin
    session: int = -1
    turn: int = 0
    # filled in by the client: each token and the time it was stamped
    times: List[float] = field(default_factory=list)
    tokens: list = field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.times) >= self.max_new


def _rng(*parts: int) -> np.random.Generator:
    return np.random.default_rng([int(p) & 0xFFFFFFFFFFFFFFFF for p in parts])


def lognormal(rng, spec: dict, n: int) -> np.ndarray:
    """Integer lognormal sizes: ``median``, ``sigma``, clipped to [lo, hi]."""
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)


def zipf_choice(rng, n_items: int, s: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_items + 1) ** s
    return rng.choice(n_items, size=n, p=w / w.sum())


def geometric(rng, mean: float, cap: int, n: int) -> np.ndarray:
    return np.minimum(rng.geometric(1.0 / mean, size=n), cap)


class Sessions:
    """Open-loop multi-turn sessions (see the module docstring)."""

    def __init__(self, mix: dict, vocab: int, seed: int, horizon_s: float):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.max_seq = mix["engine"]["max_seq_len"]
        t = mix["traffic"]
        shape = _rng(t["shape_seed"], 0)
        rate = t["session_rate"]
        n = int(rate * horizon_s * 1.5) + 16
        sp = t.get("system_prompts")
        self.n_sys = sp["count"] if sp else 0
        turns_cap = t["turns"]["cap"]
        sizes = {
            "sys": (zipf_choice(shape, self.n_sys, sp["zipf_s"], n)
                    if sp else np.full(n, -1)),
            "turns": geometric(shape, t["turns"]["mean"], turns_cap, n),
            "user": lognormal(shape, t["user_tokens"], n * turns_cap
                              ).reshape(n, turns_cap),
            "new": lognormal(shape, t["max_new_tokens"], n * turns_cap
                             ).reshape(n, turns_cap),
            "think": shape.exponential(t["think_s"], (n, turns_cap)),
        }
        gaps = shape.exponential(1.0 / rate, n)
        self.sizes = sizes
        self.arrivals = np.cumsum(gaps)
        tok = _rng(seed, 3)
        self.sys_prompts = [tok.integers(0, vocab, sp["tokens"], np.int32)
                            for _ in range(self.n_sys)] if sp else []
        self.pending: List[Req] = []        # due, not yet handed out
        self.next_session = 0
        self.key = 0

    def _tokens(self, session: int, turn: int, what: int, n: int):
        return _rng(self.seed, 4, session, turn, what).integers(
            0, self.vocab, n, np.int32)

    def _make(self, s: int, turn: int, prompt, due: float) -> Optional[Req]:
        new = int(self.sizes["new"][s, turn])
        if turn >= self.sizes["turns"][s] or len(prompt) + new > self.max_seq:
            return None
        self.key += 1
        return Req(self.key, prompt, new, due, s, turn)

    def due_until(self, t: float) -> List[Req]:
        """Requests due at or before ``t``, in order of due time."""
        while (self.next_session < len(self.arrivals)
               and self.arrivals[self.next_session] <= t):
            s = self.next_session
            self.next_session += 1
            user = self._tokens(s, 0, 0, int(self.sizes["user"][s, 0]))
            sys_i = self.sizes["sys"][s]
            prompt = (np.concatenate([self.sys_prompts[sys_i], user])
                      if sys_i >= 0 else user)
            r = self._make(s, 0, prompt, float(self.arrivals[s]))
            if r is not None:
                self.pending.append(r)
        if self.next_session >= len(self.arrivals):
            raise RuntimeError("the session schedule ran out; raise its "
                               "horizon")
        self.pending.sort(key=lambda r: r.due)
        out = [r for r in self.pending if r.due <= t]
        self.pending = [r for r in self.pending if r.due > t]
        return out

    def next_due(self) -> float:
        cand = [float(self.arrivals[self.next_session])]
        cand += [r.due for r in self.pending]
        return min(cand)

    def on_finish(self, r: Req, t: float) -> None:
        """Schedule the session's next turn a think time after ``t``."""
        s, turn = r.session, r.turn + 1
        if turn >= self.sizes["turns"][s]:
            return
        answer = self._tokens(s, turn, 1, r.max_new)
        user = self._tokens(s, turn, 0, int(self.sizes["user"][s, turn]))
        prompt = np.concatenate([r.prompt, answer, user])
        nxt = self._make(s, turn, prompt, t + float(self.sizes["think"][s, turn]))
        if nxt is not None:
            self.pending.append(nxt)


class Outstanding:
    """A fixed number of requests always outstanding (offline batch)."""

    def __init__(self, mix: dict, vocab: int, seed: int, horizon_s: float):
        t = mix["traffic"]
        self.max_seq = mix["engine"]["max_seq_len"]
        shape = _rng(t["shape_seed"], 0)
        ctx = t["contexts"]
        n_ctx = ctx["count"]
        ctx_len = shape.integers(ctx["lo"], ctx["hi"] + 1, n_ctx)
        n = t["pool"]
        sizes = {"ctx": zipf_choice(shape, n_ctx, ctx["zipf_s"], n),
                 "suffix": lognormal(shape, t["suffix_tokens"], n),
                 "new": lognormal(shape, t["max_new_tokens"], n)}
        self.sizes = sizes
        tok = _rng(seed, 3)
        self.contexts = [tok.integers(0, vocab, int(m), np.int32)
                         for m in ctx_len]
        self.seed, self.vocab = seed, vocab
        self.outstanding = t["outstanding"]
        self.i = 0
        self.pending: List[Req] = []
        self.started = False

    def _next(self, due: float) -> Req:
        i = self.i % len(self.sizes["ctx"])
        self.i += 1
        c = self.contexts[self.sizes["ctx"][i]]
        suf = _rng(self.seed, 4, self.i).integers(
            0, self.vocab, int(self.sizes["suffix"][i]), np.int32)
        prompt = np.concatenate([c, suf])
        new = int(self.sizes["new"][i])
        if len(prompt) + new > self.max_seq:
            raise ValueError(f"request of {len(prompt)} + {new} tokens does "
                             f"not fit max_seq_len {self.max_seq}")
        return Req(self.i, prompt, new, due)

    def due_until(self, t: float) -> List[Req]:
        if not self.started:
            self.started = True
            self.pending = [self._next(0.0) for _ in range(self.outstanding)]
        out = [r for r in self.pending if r.due <= t]
        self.pending = [r for r in self.pending if r.due > t]
        return out

    def next_due(self) -> float:
        return min((r.due for r in self.pending), default=float("inf"))

    def on_finish(self, r: Req, t: float) -> None:
        self.pending.append(self._next(t))


KINDS = {"sessions": Sessions, "outstanding": Outstanding}


def make(mix: dict, vocab: int, seed: int, horizon_s: float):
    return KINDS[mix["traffic"]["kind"]](mix, vocab, seed, horizon_s)
