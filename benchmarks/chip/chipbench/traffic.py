"""One general generator for every traffic mix.

A mix is a JSON file under ``traffic/`` holding only parameters of an open
loop, in which requests are due on a schedule whatever the system does:

``arrivals``    ``{"rate_per_s", "calm_s", "burst_s", "burst_factor"}``: a
                two-state Markov-modulated Poisson process, calm and burst
                episodes with exponential dwell times of mean ``calm_s`` and
                ``burst_s``, Poisson arrivals in each, the burst rate
                ``burst_factor`` times the calm rate, ``rate_per_s`` the mean;
``rows``        ``{"values": [...], "shares": [...]}``: rows per request;
``seq``         tokens per row;
``high_share``  share of requests sent at ``priority="high"``.

The work is the same for every seed.  The arrival structure (the calm and
burst episodes and the arrivals inside each), the request sizes and the
priorities all come from the mix's fixed ``structure_seed``; sizes and
priorities are exact multisets of the mix's shares.  ``--seed`` draws the
token ids (and, elsewhere, the weights).  On one TPU v5e, seeds that also
reordered the episodes and the sizes read p95 latencies up to 17 % apart,
while two runs of one seed read within 5 %: the order of the work, not
only its amount, sets the tail.

The MMPP arrival law follows ``repro.serving.sim.traces``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    rows: tuple
    shares: tuple
    seq: int
    rate_per_s: float
    calm_s: float
    burst_s: float
    burst_factor: float
    high_share: float = 0.0
    structure_seed: int = 0

    @classmethod
    def load(cls, path: str, name: str) -> "Mix":
        with open(path) as f:
            spec = json.load(f)
        arr, rows = spec["arrivals"], spec["rows"]
        mix = cls(name=name,
                  rows=tuple(int(r) for r in rows["values"]),
                  shares=tuple(float(s) for s in rows["shares"]),
                  seq=int(spec["seq"]),
                  rate_per_s=float(arr["rate_per_s"]),
                  calm_s=float(arr["calm_s"]),
                  burst_s=float(arr["burst_s"]),
                  burst_factor=float(arr["burst_factor"]),
                  high_share=float(spec.get("high_share", 0.0)),
                  structure_seed=int(spec.get("structure_seed", 0)))
        mix.validate()
        return mix

    def validate(self) -> None:
        if len(self.rows) != len(self.shares) or not self.rows:
            raise ValueError(f"{self.name}: rows and shares differ in length")
        if abs(sum(self.shares) - 1.0) > 1e-6 or min(self.shares) < 0:
            raise ValueError(f"{self.name}: shares must sum to 1")
        if min(self.rows) < 1 or self.seq < 1:
            raise ValueError(f"{self.name}: rows and seq must be positive")
        if min(self.rate_per_s, self.calm_s, self.burst_s,
               self.burst_factor) <= 0:
            raise ValueError(f"{self.name}: arrivals must be positive")

    def with_rate(self, rate_per_s: float) -> "Mix":
        return dataclasses.replace(self, rate_per_s=float(rate_per_s))


@dataclasses.dataclass(frozen=True)
class Req:
    """One request of a run: index, due time (seconds after the window
    opens), rows and priority."""
    i: int
    due: float
    rows: int
    high: bool


def _counts(shares, n: int) -> List[int]:
    """Exact counts for ``n`` draws at ``shares`` (largest remainder)."""
    raw = np.asarray(shares, float) * n
    counts = np.floor(raw).astype(int)
    for k in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[k] += 1
    return counts.tolist()


def _episodes(mix: Mix, seconds: float, rng: np.random.Generator):
    """Fixed calm/burst episodes covering ``seconds``: a list of
    (kind, duration, arrival offsets within the episode)."""
    # mean rate = (calm_s * c + burst_s * f * c) / (calm_s + burst_s)
    calm_rate = mix.rate_per_s * (mix.calm_s + mix.burst_s) / (
        mix.calm_s + mix.burst_factor * mix.burst_s)
    out, t, kind = [], 0.0, "calm"
    while t < seconds:
        mean = mix.calm_s if kind == "calm" else mix.burst_s
        rate = calm_rate * (1.0 if kind == "calm" else mix.burst_factor)
        dur = min(rng.exponential(mean), seconds - t)
        n = rng.poisson(rate * dur)
        out.append((kind, dur, np.sort(rng.uniform(0, dur, n))))
        t += dur
        kind = "burst" if kind == "calm" else "calm"
    return out


def open_schedule(mix: Mix, seconds: float) -> List[Req]:
    """Every request due in a window of ``seconds``, in due order."""
    rng = np.random.default_rng(mix.structure_seed)
    dues, t = [], 0.0
    for _kind, dur, offsets in _episodes(mix, seconds, rng):
        dues.extend(t + offsets)
        t += dur
    n = len(dues)
    rows = np.repeat(mix.rows, _counts(mix.shares, n))
    rng.shuffle(rows)
    high = np.zeros(n, bool)
    high[:_counts((mix.high_share, 1 - mix.high_share), n)[0]] = True
    rng.shuffle(high)
    return [Req(i, float(d), int(r), bool(h))
            for i, (d, r, h) in enumerate(zip(dues, rows, high))]


def tokens(seed: int, i: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """Token ids of request ``i``, uniform over the vocabulary."""
    rng = np.random.default_rng([seed, i])
    return rng.integers(0, vocab, (rows, seq), dtype=np.int32)


def warm_sequence(sizes, batch: int) -> List[int]:
    """Request sizes that, packed back to back, start a request of every
    size at every row offset modulo ``batch``: an Eulerian circuit over the
    offsets, each (offset, size) pair used once.  This reaches every way a
    request's rows can be cut at compiled-batch boundaries."""
    sizes = sorted(set(int(s) for s in sizes))
    unused = {r: list(sizes) for r in range(batch)}
    # Hierholzer's algorithm on the graph r -> (r + n) % batch
    stack, circuit = [(0, None)], []
    while stack:
        r, n_in = stack[-1]
        if unused[r]:
            n = unused[r].pop()
            stack.append(((r + n) % batch, n))
        else:
            stack.pop()
            if n_in is not None:
                circuit.append(n_in)
    circuit.reverse()
    return circuit


def check_seed(seed: Optional[int]) -> int:
    if seed is None or seed < 0:
        raise ValueError("--seed must be a whole number >= 0")
    return int(seed)
