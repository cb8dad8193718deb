"""The one traffic generator: every mix is a data file it reads.

A mix fixes a *catalog* of request sizes (and, for an open loop, of
inter-arrival gaps), drawn from ``CATALOG_SEED``.  The run's ``--seed``
only permutes that catalog and draws the prompt tokens, so every seed
offers the same set of sizes and arrivals in another order: the work is
the seed's to shuffle, not to change.

Keys of a mix file:

* ``loop``: ``"closed"`` (``clients`` callers, each waits for its reply;
  client ``c`` of ``n`` keeps ``(c + 0.5) / n`` of its first request's
  output, so the slots start the window at spread-out points of their
  requests, as in steady state) or ``"open"`` (Poisson arrivals at
  ``rate`` requests/s);
* ``requests``: catalog length (requests cycle through it if a run
  needs more);
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``, in tokens;
* ``ramp_requests`` (open loop): the first this many catalog entries
  (sizes and gaps) are the ramp, sent before the window opens; the rest
  are the window's.  Each part is permuted on its own, so every seed
  offers the window the same requests at the same rate, and the window
  opens at the same time after the first arrival: the sum of the
  ramp's gaps.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

CATALOG_SEED = 0


@dataclass(frozen=True)
class Request:
    idx: int                 # position in this run's request sequence
    prompt: np.ndarray       # int32 token ids
    max_new: int
    offset_s: float | None   # open loop: due time after the first arrival


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from ``dist``, clipped to ``[min, max]``."""

    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        x = np.exp(np.log(float(dist["median"]))
                   + float(dist["sigma"]) * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def catalog(mix: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray | None]:
    """The mix's fixed sizes: (prompt lengths, output lengths, gaps in s
    or None), independent of the run's seed."""

    n = int(mix["requests"])
    rng = np.random.default_rng(CATALOG_SEED)
    p = lengths(mix["prompt"], n, rng)
    o = lengths(mix["output"], n, rng)
    gaps = None
    if mix["loop"] == "open":
        gaps = rng.exponential(1.0 / float(mix["rate"]), n)
    return p, o, gaps


def ramp_s(mix: dict) -> float:
    """Open loop: seconds from the first arrival to the window."""

    _, _, gaps = catalog(mix)
    return float(gaps[:int(mix["ramp_requests"])].sum())


class Generator:
    """The run's request sequence: request ``k`` takes catalog entry
    ``perm[k % n]``; its tokens are drawn from the run's seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.vocab = int(vocab)
        self.p, self.o, gaps = catalog(mix)
        self.n = len(self.p)
        self.rng = np.random.default_rng(seed)
        r = int(mix.get("ramp_requests", 0))
        self.perm = np.concatenate([self.rng.permutation(r),
                                    r + self.rng.permutation(self.n - r)])
        self.gperm = np.concatenate([self.rng.permutation(r),
                                     r + self.rng.permutation(self.n - r)])
        self.gaps = gaps
        self.k = 0
        self.t = 0.0

    def next(self) -> Request:
        k = self.k
        self.k += 1
        j = int(self.perm[k % self.n])
        offset = None
        if self.gaps is not None:
            offset = self.t
            self.t += float(self.gaps[int(self.gperm[k % self.n])])
        prompt = self.rng.integers(0, self.vocab, int(self.p[j]),
                                   dtype=np.int32)
        return Request(idx=k, prompt=prompt, max_new=int(self.o[j]),
                       offset_s=offset)


def staggered(req: Request, client: int, clients: int) -> Request:
    """A closed-loop client's first request, cut to ``(c + 0.5) / n`` of
    its output so the window opens on slots at spread-out progress."""

    keep = max(1, int(round(req.max_new * (client + 0.5) / clients)))
    return Request(idx=req.idx, prompt=req.prompt, max_new=keep,
                   offset_s=req.offset_s)
