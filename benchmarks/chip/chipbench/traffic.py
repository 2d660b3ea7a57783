"""One seeded generator for every traffic mix.

A mix is a data file, `traffic/<name>.json`, of parameters only:

  loop          "open" (arrivals on a schedule) or "closed" (each client
                sends its next request when the last one finished)
  unit          "request" (independent prompts) or "session" (multi-turn
                conversations that re-send their whole history)
  rate_per_s    open loop: Poisson arrivals of the unit, per second
  clients       closed loop: concurrent clients (requests or sessions)
  lead_s        open loop: arrivals start this long before the window
  window_opens  "lead_end" (open loop) or "slots_full" (the window opens
                once every slot holds a request and as many requests as
                there are slots have begun to decode)
  prompt/output length distributions of independent requests
  session       {system_pool, system_len, turns, user_len, reply_len,
                 think_s, max_context} for session traffic

A distribution is {"dist": "lognormal", "median", "sigma", "min", "max"}
(truncated to [min, max]) or {"dist": "uniform", "min", "max"}.

Every draw is stratified: n draws of a distribution are its quantiles at
(i + 0.5) / n, laid out in one fixed shuffled order (`ORDER`); then each
seed shuffles every group of `GROUP` values adjacent in size among the
places they hold. So every seed offers the same sizes, gaps and think
times in an order of its own that keeps each value's place up to a swap
with a near-equal one, and draws its own token ids (and weights). On one
chip a p95 over some 30 requests moved by up to 45% from one seed to the
next when each seed shuffled them within blocks of 8 in time: the order
decides which long prompts queue behind each other.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()
ORDER = 0        # the seed of the one order of sizes, gaps and think times
GROUP = 2        # each seed swaps values within groups this many ranks wide


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """An independent numpy generator per (seed, stream); any whole seed,
    however large."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def quantile(dist: Dict, u: np.ndarray) -> np.ndarray:
    """Quantiles of a length or time distribution at probabilities u."""
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["dist"] == "uniform":
        return lo + u * (hi - lo)
    if dist["dist"] == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        f_lo = _NORMAL.cdf((math.log(lo) - mu) / sigma)
        f_hi = _NORMAL.cdf((math.log(hi) - mu) / sigma)
        p = f_lo + np.asarray(u) * (f_hi - f_lo)
        z = np.asarray([_NORMAL.inv_cdf(float(x)) for x in np.ravel(p)])
        return np.exp(mu + sigma * z).reshape(np.shape(u))
    raise ValueError(f"unknown distribution {dist['dist']!r}")


def reorder(x: np.ndarray, stream: int, seed: int) -> np.ndarray:
    """x in the fixed order of `stream`; then the seed shuffles each group
    of `GROUP` values adjacent in size among the places they hold."""
    x = x[seed_rng(ORDER, stream).permutation(len(x))]
    rank = np.argsort(np.argsort(x, kind="stable"), kind="stable")
    rng = seed_rng(seed, 100 + stream)
    out = x.copy()
    for g in range(0, len(x), GROUP):
        at = np.flatnonzero((rank >= g) & (rank < g + GROUP))
        out[at] = x[at][rng.permutation(len(at))]
    return out


def stratified(dist: Dict, n: int, stream: int, seed: int, *,
               integer: bool = True) -> np.ndarray:
    """n draws: the distribution's quantiles at (i + 0.5)/n, reordered."""
    u = (np.arange(n) + 0.5) / n
    x = quantile(dist, u)
    if integer:
        x = np.clip(np.floor(x + 0.5), dist["min"], dist["max"]).astype(
            np.int64)
    return reorder(x, stream, seed)


def poisson_arrivals(rate: float, n: int, seed: int) -> np.ndarray:
    """n arrival times from 0: stratified exponential gaps, reordered."""
    u = (np.arange(n) + 0.5) / n
    gaps = reorder(-np.log1p(-u) / rate, 2, seed)
    return np.cumsum(gaps) - gaps.mean()


@dataclasses.dataclass(eq=False)
class Item:
    """One request to send: due at `due` seconds on the run's clock."""
    due: float
    prompt: np.ndarray
    max_new: int
    session: Optional[int] = None
    turn: int = 0
    think_s: float = 0.0            # sessions: pause before the next turn


class Source:
    """The requests of one run: `initial()` at the start, then `on_done()`
    after each completion (closed loops and session follow-ups)."""

    def __init__(self, mix: Dict, seed: int, *, vocab: int,
                 horizon_s: float, pool: int = 4096):
        self.mix, self.vocab = mix, int(vocab)
        self.rng = seed_rng(seed, 1)
        self.closed = mix["loop"] == "closed"
        self.sessions = mix.get("unit", "request") == "session"
        if self.closed:
            n_units = pool
            self.due0 = np.zeros(int(mix["clients"]))
        else:
            n_units = max(1, int(math.ceil(mix["rate_per_s"] * horizon_s)))
            self.due0 = poisson_arrivals(mix["rate_per_s"], n_units, seed)
        self._next = 0
        if self.sessions:
            self._init_sessions(n_units, seed)
        else:
            self.plens = stratified(mix["prompt"], n_units, 3, seed)
            self.olens = stratified(mix["output"], n_units, 4, seed)

    # --------------------------------------------------------------- tokens
    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, int(n), dtype=np.int32)

    # -------------------------------------------------------- independent
    def _request(self, due: float) -> Item:
        i = self._next % len(self.plens)
        self._next += 1
        return Item(due=float(due), prompt=self._tokens(self.plens[i]),
                    max_new=int(self.olens[i]))

    # ------------------------------------------------------------ sessions
    def _init_sessions(self, n: int, seed: int) -> None:
        s = self.mix["session"]
        self.system = [self._tokens(k) for k in stratified(
            s["system_len"], int(s["system_pool"]), 5, seed)]
        self.sys_of = stratified(
            {"dist": "uniform", "min": 0, "max": len(self.system) - 0.5},
            n, 6, seed, integer=False).astype(np.int64)
        self.n_turns = stratified(
            {"dist": "uniform", "min": s["turns"]["min"],
             "max": s["turns"]["max"] + 0.999}, n, 7, seed,
            integer=False).astype(np.int64)
        m = n * int(s["turns"]["max"])
        self.user = stratified(s["user_len"], m, 8, seed)
        self.reply = stratified(s["reply_len"], m, 9, seed)
        self.think = stratified(s["think_s"], m, 10, seed,
                                integer=False)
        self._turn_next = 0
        self.history: Dict[int, np.ndarray] = {}
        self.left: Dict[int, int] = {}

    def _turn(self, sid: int, due: float, turn: int) -> Optional[Item]:
        """The next turn of session `sid`, or None where it would pass the
        context limit."""
        k = self._turn_next % len(self.user)
        user, reply = int(self.user[k]), int(self.reply[k])
        hist = self.history[sid]
        if hist.shape[0] + user + reply > self.mix["session"]["max_context"]:
            return None
        self._turn_next += 1
        prompt = np.concatenate([hist, self._tokens(user)])
        return Item(due=float(due), prompt=prompt, max_new=reply,
                    session=sid, turn=turn, think_s=float(self.think[k]))

    def _session(self, due: float) -> Optional[Item]:
        sid = self._next
        i = sid % len(self.sys_of)
        self._next += 1
        self.history[sid] = self.system[int(self.sys_of[i])]
        self.left[sid] = int(self.n_turns[i])
        return self._turn(sid, due, 0)

    # ------------------------------------------------------------ protocol
    def initial(self) -> List[Item]:
        make = self._session if self.sessions else self._request
        return [it for it in (make(d) for d in self.due0) if it is not None]

    def on_done(self, item: Item, out_tokens, t: float) -> List[Item]:
        """Follow-ups once `item` finished at time t with `out_tokens`."""
        if self.sessions:
            sid = item.session
            self.left[sid] -= 1
            if self.left[sid] > 0:
                self.history[sid] = np.concatenate(
                    [item.prompt, np.asarray(out_tokens, np.int32)])
                nxt = self._turn(sid, t + item.think_s, item.turn + 1)
                if nxt is not None:
                    return [nxt]
            self.history.pop(sid, None)
            nxt = self._session(t) if self.closed else None
            return [] if nxt is None else [nxt]
        return [self._request(t)] if self.closed else []
