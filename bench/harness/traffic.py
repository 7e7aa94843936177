"""The one traffic generator: sessions of prompts, drawn from the seed.

A traffic file (``bench/traffic/<name>.json``) gives the parameters:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  session when the last one is answered) or ``"open"`` (sessions arrive
  on a schedule at ``rate_per_s``, whatever the system's state);
* ``requests_per_session``, ``microbatch``, ``decode_tokens``;
* ``prompt_len``: a mix ``{length: share}``, one length per session;
* ``engine``: the ``EngineManager`` layout (nodes, workers, concurrency).

The work of a run is drawn so that every seed gets the same amount of it:
the prompt lengths of the first ``n`` sessions hold each length in its
share of ``n`` (largest remainder), and the open loop's inter-arrival gaps
are the quantiles of the exponential distribution at ``rate_per_s``.  The
seed only orders them and draws the prompt tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterator, List, Sequence

import numpy as np

# independent random streams of one seed
STREAM_WEIGHTS, STREAM_WARMUP, STREAM_SCHEDULE, STREAM_CHECK = 0, 1, 2, 3
STREAM_CLIENT0 = 100


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def weight_key_int(seed: int) -> int:
    """The 31-bit integer the model weights are keyed by for ``seed``."""
    return int(np.random.SeedSequence([int(seed), STREAM_WEIGHTS])
               .generate_state(1)[0]) & 0x7FFFFFFF


def apportion(mix: Dict[int, float], n: int) -> List[int]:
    """``n`` lengths with each length of ``mix`` in its share, by largest
    remainder (ties to the longer length), in mix order."""
    lengths = sorted(mix)
    total = sum(mix.values())
    quotas = [n * mix[s] / total for s in lengths]
    counts = [math.floor(q) for q in quotas]
    order = sorted(range(len(lengths)),
                   key=lambda i: (quotas[i] - counts[i], lengths[i]),
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [s for s, c in zip(lengths, counts) for _ in range(c)]


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the midpoint quantiles of an exponential
    distribution, scaled to mean ``1 / rate``."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    return gaps * (1.0 / rate) / gaps.mean()


@dataclass(frozen=True)
class Mix:
    loop: str
    requests: int
    microbatch: int
    decode_tokens: int
    prompt_len: Dict[int, float]
    engine: Dict[str, int]
    clients: int = 0
    rate_per_s: float = 0.0
    late_s: float = 60.0
    check_requests: int = 8

    @classmethod
    def from_file(cls, d: Dict) -> "Mix":
        return cls(loop=d["loop"], requests=int(d["requests_per_session"]),
                   microbatch=int(d["microbatch"]),
                   decode_tokens=int(d["decode_tokens"]),
                   prompt_len={int(k): float(v)
                               for k, v in d["prompt_len"].items()},
                   engine={k: int(v) for k, v in d["engine"].items()},
                   clients=int(d.get("clients", 0)),
                   rate_per_s=float(d.get("rate_per_s", 0.0)),
                   late_s=float(d.get("late_s", 60.0)),
                   check_requests=int(d.get("check_requests", 8)))

    def lengths(self) -> List[int]:
        return sorted(self.prompt_len)


@dataclass
class Session:
    index: int
    prompts: np.ndarray          # (requests, prompt_len) int32
    at: float = 0.0              # open loop: seconds after the window opens
    client: int = 0              # closed loop: the client that sends it

    @property
    def prompt_len(self) -> int:
        return int(self.prompts.shape[1])


def _prompts(g: np.random.Generator, requests: int, length: int,
             vocab: int) -> np.ndarray:
    return g.integers(0, vocab, size=(requests, length), dtype=np.int32)


def warmup_sessions(mix: Mix, seed: int, vocab: int) -> List[Session]:
    """One session of every prompt length the mix uses."""
    g = rng(seed, STREAM_WARMUP)
    return [Session(i, _prompts(g, mix.requests, s, vocab))
            for i, s in enumerate(mix.lengths())]


def open_schedule(mix: Mix, seed: int, seconds: float,
                  vocab: int) -> List[Session]:
    """The open loop's sessions: ``round(rate * seconds)`` arrivals in
    ``[0, seconds)``, the first at 0, with fixed gaps and lengths in an
    order drawn from the seed."""
    n = max(1, round(mix.rate_per_s * seconds))
    g = rng(seed, STREAM_SCHEDULE)
    gaps = g.permutation(exponential_gaps(n / seconds, n))
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    lengths = g.permutation(apportion(mix.prompt_len, n))
    return [Session(i, _prompts(g, mix.requests, int(s), vocab), float(t))
            for i, (t, s) in enumerate(zip(at, lengths))]


def client_sessions(mix: Mix, seed: int, client: int,
                    vocab: int, block: int = 20) -> Iterator[Session]:
    """A closed-loop client's endless sessions; every ``block`` sessions
    hold the mix's lengths in their shares."""
    g = rng(seed, STREAM_CLIENT0 + client)
    i = 0
    while True:
        for s in g.permutation(apportion(mix.prompt_len, block)):
            yield Session(i, _prompts(g, mix.requests, int(s), vocab),
                          client=client)
            i += 1


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The nearest-rank ``q``-th percentile (``q`` in (0, 100]): the
    smallest value with at least ``q`` percent of the values at or below
    it.  Misses enter as ``inf`` and so rank above every answer."""
    if not len(values):
        return math.inf
    v = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return float(v[rank - 1])
