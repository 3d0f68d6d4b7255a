"""The benchmark's data, made from ``--seed`` alone.

The archive follows the repository's synthetic genomes (uniform random
bases with planted repeats; ``repro.data.genome.synthesize_genome``),
copied here so that no change to the program can move the yardstick.
The reads follow a traffic mix's parameters; every seed gets the same
sizes and the same arrivals, in another order, so that a seed changes
which bases are asked for and not how much work they are.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

REPEAT_FRACTION = 0.3
REPEAT_UNIT = 500
REPEAT_LIBRARY = 8
KINDS = ("indexed", "substituted", "random")


def archive(n_files: int, genome_len: int, seed: int) -> np.ndarray:
    """(n_files, genome_len) uint8 base codes, one genome per file."""
    rng = np.random.default_rng([seed, 1])
    genomes = rng.integers(0, 4, size=(n_files, genome_len), dtype=np.uint8)
    if genome_len > 2 * REPEAT_UNIT:
        n_repeat = int(genome_len * REPEAT_FRACTION)
        for g in genomes:
            library = rng.integers(0, 4, size=(REPEAT_LIBRARY, REPEAT_UNIT),
                                   dtype=np.uint8)
            placed = 0
            while placed < n_repeat:
                start = int(rng.integers(0, genome_len - REPEAT_UNIT))
                g[start:start + REPEAT_UNIT] = library[
                    int(rng.integers(0, REPEAT_LIBRARY))]
                placed += REPEAT_UNIT
    return genomes


@dataclasses.dataclass
class Request:
    """One query read and what it was made from."""

    read: np.ndarray
    kind: str           # one of KINDS
    file: int           # source file, -1 for a random read
    scheduled: Optional[float] = None   # due time (open loop)
    sent: Optional[float] = None
    dispatched: Optional[float] = None  # its batch's dispatch
    done: Optional[float] = None
    answer: Optional[tuple] = None      # the file ids served
    error: Optional[str] = None


def read_lengths(spec) -> np.ndarray:
    """The fixed multiset of read lengths of a mix."""
    if isinstance(spec, dict):
        lo, hi = spec["uniform"]
        return np.arange(lo, hi + 1)
    return np.asarray(spec)


def _kinds(mix: dict) -> list[str]:
    """One block of kinds, in the mix's whole-number proportions."""
    return [kind for kind in KINDS for _ in range(int(mix.get(kind, 0)))]


class ReadStream:
    """Endless reads of one traffic mix over one archive.

    Within every block of ``len(lengths) * len(kinds)`` requests each
    length and each kind comes equally often, shuffled by the seed, so
    that seeds differ in order and bases only.
    """

    def __init__(self, genomes: np.ndarray, traffic: dict, seed: int,
                 stream: int = 0):
        self.genomes = genomes
        self.rng = np.random.default_rng([seed, 2, stream])
        self.lengths = read_lengths(traffic["read_lengths"])
        self.kinds = _kinds(traffic["mix"])
        self._plan: list = []

    def _refill(self) -> None:
        lengths = np.tile(self.lengths, len(self.kinds))
        kinds = np.repeat(np.arange(len(self.kinds)), len(self.lengths))
        order = self.rng.permutation(lengths.size)
        self._plan = list(zip(lengths[order].tolist(),
                              kinds[order].tolist()))[::-1]

    def next(self) -> Request:
        if not self._plan:
            self._refill()
        length, kind = self._plan.pop()
        kind = self.kinds[kind]
        n_files, genome_len = self.genomes.shape
        if kind == "random":
            read = self.rng.integers(0, 4, size=length, dtype=np.uint8)
            return Request(read=read, kind=kind, file=-1)
        f = int(self.rng.integers(0, n_files))
        start = int(self.rng.integers(0, genome_len - length + 1))
        read = self.genomes[f, start:start + length].copy()
        if kind == "substituted":
            # one sequencing error: a different base at one position
            pos = int(self.rng.integers(0, length))
            read[pos] = (read[pos] + self.rng.integers(1, 4)) % 4
        return Request(read=read, kind=kind, file=f)

    def take(self, n: int) -> list[Request]:
        return [self.next() for _ in range(n)]


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Poisson arrival offsets over ``seconds``: the gaps are the same fixed
    set of exponential quantiles for every seed, in the seed's order."""
    n = max(int(round(rate_per_s * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    gaps = np.random.default_rng([seed, 3]).permutation(gaps)
    return np.cumsum(gaps) - gaps[0]
