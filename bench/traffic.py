"""The one traffic generator.  A mix is a JSON file of parameters
(``bench/traffic/<mix>.json``); this module turns it and a seed into
requests.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps, in another order.  Requests come in blocks (one per
round of the closed loop's streams, ``block`` requests in the open loop),
and each block holds a stratified set of quantiles of every distribution,
shuffled by the seed.  Any whole number of blocks therefore does the same
work on every seed; only the order and the prompt tokens change.

Mix keys:

  loop          "closed": ``streams`` clients, each sends its next request
                when the previous one finished; set-up sends every stream's
                first request and steps the engine until no admitted request
                is still prefilling, so the window opens on the decode that
                follows.  "open": requests are due at arrival times with
                mean rate ``rate_per_s``, whatever the server does.
  interarrival  open loop only: {"dist": "exponential"} (Poisson)
  prompt_len,   {"dist": "uniform", "min", "max"} or
  output_len    {"dist": "lognormal", "median", "sigma", "min", "max"}
  rounds        closed loop: requests each stream can send (a stream that
                runs out stops sending)
  block         open loop: requests per stratified block (default 64)
  engine        engine sizing, passed to the program as it stands
  sample        how many finished and in-flight requests the check compares
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    index: int           # position in the seed's request order
    stream: int          # closed loop: the client; open loop: -1
    due_s: float         # open loop: due time after the window opens
    prompt: np.ndarray   # (plen,) int32
    max_new: int


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *tags])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles ((i + 0.5) / n) of a length distribution,
    as whole numbers within its bounds."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = int(dist["min"]), int(dist["max"])
        return (lo + np.floor(u * (hi - lo + 1))).astype(np.int64)
    if kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        v = np.rint(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
        return np.clip(v, int(dist["min"]), int(dist["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def gaps(spec: dict, rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps (seconds) with mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    kind = spec.get("dist", "exponential")
    if kind == "exponential":
        return -np.log1p(-u) / rate
    raise ValueError(f"unknown inter-arrival distribution {kind!r}")


def _blocked(values: np.ndarray, n_blocks: int, seed: int, tag: int
             ) -> np.ndarray:
    """``values`` (one stratified block) shuffled anew for each block."""
    return np.concatenate([_rng(seed, tag, b).permutation(values)
                           for b in range(n_blocks)])


def make_requests(mix: dict, seed: int, seconds: float, vocab: int
                  ) -> List[Req]:
    """The seed's requests in order.  Open loop: sorted by due time, enough
    that arrivals outlast a window of ``seconds``.  Closed loop: request i
    belongs to stream i % streams, and each stream sends its own in
    order, ``rounds`` of them."""
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        block = int(mix.get("block", 64))
        n_blocks = int(math.ceil((rate * seconds * 1.5 + 16) / block))
        g = _blocked(gaps(mix.get("interarrival", {}), rate, block),
                     n_blocks, seed, 1)
        due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        streams = None
    elif mix["loop"] == "closed":
        block = streams = int(mix["streams"])
        n_blocks = int(mix["rounds"])
        due = np.zeros(block * n_blocks)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plen = _blocked(quantiles(mix["prompt_len"], block), n_blocks, seed, 2)
    olen = _blocked(quantiles(mix["output_len"], block), n_blocks, seed, 3)
    out = []
    for i in range(block * n_blocks):
        toks = _rng(seed, 4, i).integers(0, vocab, int(plen[i]),
                                         dtype=np.int64).astype(np.int32)
        out.append(Req(index=i, stream=-1 if streams is None else i % streams,
                       due_s=float(due[i]), prompt=toks,
                       max_new=int(olen[i])))
    return out
