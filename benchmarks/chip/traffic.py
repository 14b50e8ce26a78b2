"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``traffic/<name>.json``) fixes the loop (open, at a fixed rate, or
closed, with a fixed number of clients), the shared prefixes and how
popular each is, optional multi-turn sessions, and the length
distributions of the new prompt text and of the reply.

Every seed gets the same amount of work: lengths, gaps between arrivals,
turn counts and prefix choices are each a fixed set of quantiles of their
distribution, which the seed only puts in another order.  The seed also
draws every token id.  So runs with different seeds differ in what they
send and in which order, not in how much.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # (n,) int32
    max_new_tokens: int
    due_s: float = 0.0          # offset from the window start (open loop;
                                # a closed-loop client's first request)


@dataclasses.dataclass
class Traffic:
    loop: str                   # "open" | "closed"
    arrivals: list[Request]     # open loop, in due order
    clients: list[list[Request]]  # closed loop: each client's requests


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_set(spec: dict, n: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """``n`` lengths at the quantiles of a lognormal (median, sigma),
    clipped to [min, max], in an order drawn from ``rng``."""
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    v = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    v = np.clip(v, spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(v)


def exponential_gaps(n: int, total_s: float, rng: np.random.Generator
                     ) -> np.ndarray:
    """``n`` gaps of a Poisson process at the quantiles of the exponential,
    scaled so the last arrival falls inside ``total_s``, shuffled."""
    g = -np.log1p(-_quantiles(n))
    g *= total_s * n / (n + 1) / g.sum()
    return rng.permutation(g)


def zipf_set(n: int, count: int, s: float, rng: np.random.Generator
             ) -> np.ndarray:
    """``n`` picks among ``count`` items with Zipf(s) popularity."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    picks = np.searchsorted(cdf, _quantiles(n), side="right")
    return rng.permutation(np.minimum(picks, count - 1))


def geometric_set(n: int, mean: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """``n`` counts >= 1 of a geometric distribution with this mean."""
    p = 1.0 / mean
    u = _quantiles(n)
    k = np.ceil(np.log1p(-u) / math.log1p(-p)) if p < 1 else np.ones(n)
    return rng.permutation(np.maximum(k, 1).astype(np.int64))


def max_prompt(mix: dict) -> int:
    pre = mix["shared_prefix"]["tokens"] if mix.get("shared_prefix") else 0
    longest = pre + mix["new_tokens"]["max"]
    if mix.get("sessions"):
        longest = mix["prompt_cap"]
    return min(longest, mix["prompt_cap"])


def min_prompt(mix: dict) -> int:
    pre = mix["shared_prefix"]["tokens"] if mix.get("shared_prefix") else 0
    return pre + mix["new_tokens"]["min"]


def max_rows(mix: dict) -> int:
    """KV rows the longest request writes (prompt + reply - 1)."""
    return max_prompt(mix) + mix["reply_tokens"]["max"] - 1


class _Prompts:
    """Builds prompts from shared prefixes, sessions and fresh text."""

    def __init__(self, mix: dict, vocab: int, rng: np.random.Generator,
                 n: int):
        self.mix, self.vocab, self.rng = mix, vocab, rng
        sp = mix.get("shared_prefix")
        self.prefixes = ([self._ids(sp["tokens"]) for _ in range(sp["count"])]
                         if sp else [])
        self.pick = (zipf_set(n, sp["count"], sp.get("zipf_s", 0.0), rng)
                     if sp else np.zeros(n, np.int64))
        self.new = lognormal_set(mix["new_tokens"], n, rng)
        self.reply = lognormal_set(mix["reply_tokens"], n, rng)
        self.i = 0

    def _ids(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, n, dtype=np.int32)

    def request(self, history: Optional[list] = None,
                prefix: Optional[int] = None) -> tuple[Request, np.ndarray]:
        """The next request; ``history`` (a session's earlier text, a list
        of id arrays) is kept whole from its newest end within the cap."""
        i, self.i = self.i, self.i + 1
        p = int(self.pick[i]) if prefix is None else prefix
        pre = self.prefixes[p] if self.prefixes else np.zeros(0, np.int32)
        new = self._ids(int(self.new[i]))
        cap = self.mix["prompt_cap"]
        hist = list(history or [])
        while hist and len(pre) + sum(map(len, hist)) + len(new) > cap:
            hist.pop(0)
        prompt = np.concatenate([pre, *hist, new])[:cap].astype(np.int32)
        return Request(prompt, int(self.reply[i])), new


def generate(mix: dict, vocab: int, seed: int, seconds: float) -> Traffic:
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        n = max(1, round(mix["rate_per_s"] * seconds))
        due = np.cumsum(exponential_gaps(n, seconds, rng))
        b = _Prompts(mix, vocab, rng, n)
        reqs = _sessions(mix, b, n, rng) if mix.get("sessions") else [
            b.request()[0] for _ in range(n)]
        for r, t in zip(reqs, due):
            r.due_s = float(t)
        return Traffic("open", reqs, [])
    if mix["loop"] == "closed":
        c, per = mix["clients"], mix["requests_per_client"]
        b = _Prompts(mix, vocab, rng, c * per)
        flat = [b.request()[0] for _ in range(c * per)]
        clients = [flat[k::c] for k in range(c)]
        for k, reqs in enumerate(clients):
            reqs[0].due_s = k * mix.get("start_spread_s", 0.0) / c
        return Traffic("closed", [], clients)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def _sessions(mix: dict, b: _Prompts, n: int, rng: np.random.Generator
              ) -> list[Request]:
    """``n`` turns of multi-turn sessions.  A pool of open sessions takes
    turns in rotation, so one session's turns are about ``turn_gap_s``
    apart; a session that has sent its last turn makes room for a new
    one.  Each turn's prompt is the session's prefix, its history (earlier
    turns and synthetic replies) and the new turn."""
    ses = mix["sessions"]
    pool_size = max(1, round(mix["rate_per_s"] * ses["turn_gap_s"]))
    turns = list(geometric_set(n, ses["mean_turns"], rng))
    prefix_of = list(b.pick)
    pool: list[dict] = []
    out = []

    def new_session() -> dict:
        return {"left": int(turns.pop()), "prefix": int(prefix_of.pop()),
                "history": []}

    for k in range(n):
        slot = k % pool_size
        if slot == len(pool):
            pool.append(new_session())
        elif pool[slot]["left"] == 0:
            pool[slot] = new_session()
        s = pool[slot]
        req, new = b.request(s["history"], prefix=s["prefix"])
        s["history"] += [new, b._ids(req.max_new_tokens)]
        s["left"] -= 1
        out.append(req)
    return out
