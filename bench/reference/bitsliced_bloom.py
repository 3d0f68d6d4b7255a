"""Plain reference for a bit-sliced (COBS-style) Bloom-filter archive.

numpy only, and nothing of the system under test: the hash families are
written out here from their definitions, the index is a sorted array of
``(location, file)`` keys instead of a bit matrix, and a query is a
lookup of every location of every kmer of the read.

The semantics, as a deployment's configuration states them:

* Every file is a Bloom filter over the rows ``[0, m)``. A kmer of a file
  sets, for each repetition ``j < eta``, the bit of row ``psi_j(kmer)``
  in that file's column.
* A read of ``n`` bases has ``n - k + 1`` kmers (stride 1). A kmer is in
  a file when all ``eta`` of its rows are set in the file's column.
* A file answers a read when at least ``ceil(theta * n_kmers)`` of the
  read's kmer positions are in it (theta = 1: every kmer).

The hash families are the 32-bit ones the served index uses:

* ``idl`` (IDentity with Locality, arXiv:2406.14901): a densified
  one-permutation MinHash over the kmer's ``t``-mers picks an aligned
  ``L``-row window of the repetition's partition (rho_1), and a hash of
  the kmer itself picks the row inside it (rho_2).
* ``rh``: one seeded hash of the kmer per repetition, uniform over the
  repetition's partition (the scheme COBS ships, arXiv:1905.09624).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

U32 = np.uint32
MAX32 = U32(0xFFFFFFFF)
GOLDEN = U32(0x9E3779B9)
M1 = U32(0x85EBCA6B)
M2 = U32(0xC2B2AE35)
SALT_MINHASH = 0x0D0F
SALT_LOCAL = 0x10CA
SALT_RH = 0x5EED


@dataclasses.dataclass(frozen=True)
class Scheme:
    """The hash parameters of one deployment."""

    name: str        # "idl" | "rh"
    k: int
    t: int
    L: int
    eta: int
    m: int

    @property
    def part(self) -> int:
        """Rows per repetition: ``m / eta`` rounded down to whole windows."""
        return (self.m // self.eta) // self.L * self.L


def scheme_of(cfg: dict) -> Scheme:
    return Scheme(name=cfg["scheme"], k=cfg["k"], t=cfg["t"], L=cfg["L"],
                  eta=cfg["eta"], m=cfg["m"])


# ---------------------------------------------------------------------------
# 32-bit hashing (numpy uint32 arithmetic wraps mod 2^32, as the lanes do).
# ---------------------------------------------------------------------------

def mix32(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finalizer."""
    x = x ^ (x >> U32(16))
    x = x * M1
    x = x ^ (x >> U32(13))
    x = x * M2
    return x ^ (x >> U32(16))


def hash_pair(hi: np.ndarray, lo: np.ndarray, seed: int) -> np.ndarray:
    """Seeded 32-bit hash of a 62-bit kmer held as two uint32 halves."""
    s = U32(seed)
    c1 = U32((int(s) * int(GOLDEN)) & 0xFFFFFFFF) | U32(1)
    c2 = U32(((int(s) ^ 0xDEADBEEF) * int(M1)) & 0xFFFFFFFF) | U32(1)
    h = mix32(lo * c1 + c2)
    return mix32(h ^ (hi * c2 + c1))


def to_range(h: np.ndarray, n: int) -> np.ndarray:
    """Map a uint32 hash onto ``[0, n)`` (multiply-shift, exact)."""
    if n < (1 << 15):
        hi16, lo16 = h >> U32(16), h & U32(0xFFFF)
        return (hi16 * U32(n) + ((lo16 * U32(n)) >> U32(16))) >> U32(16)
    if n & (n - 1) == 0:
        return h >> U32(32 - (n.bit_length() - 1))
    return h % U32(n)


# ---------------------------------------------------------------------------
# kmers of base-code sequences (last axis; any leading axes).
# ---------------------------------------------------------------------------

def pack(codes: np.ndarray, width: int) -> np.ndarray:
    """Every stride-1 ``width``-mer (width <= 16) as a uint32, 2 bits a base,
    first base highest."""
    codes = codes.astype(U32)
    out = codes.shape[-1] - width + 1
    acc = np.zeros(codes.shape[:-1] + (out,), U32)
    for j in range(width):
        acc = (acc << U32(2)) | codes[..., j:j + out]
    return acc


def kmer_halves(codes: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Every kmer as ``(hi, lo)``: lo holds its last min(k, 16) bases."""
    n_lo = min(k, 16)
    n_hi = k - n_lo
    out = codes.shape[-1] - k + 1
    lo = pack(codes[..., n_hi:], n_lo)[..., :out]
    if n_hi == 0:
        return np.zeros_like(lo), lo
    return pack(codes, n_hi)[..., :out], lo


def window_min(a: np.ndarray, w: int) -> np.ndarray:
    """Minimum over every stride-1 window of ``w`` along the last axis."""
    out = a.shape[-1] - w + 1
    m = a[..., :out].copy()
    for i in range(1, w):
        np.minimum(m, a[..., i:i + out], out=m)
    return m


def idl_rows(s: Scheme, codes: np.ndarray) -> np.ndarray:
    """(..., eta, n_kmers) rows of every kmer under IDL."""
    w = s.k - s.t + 1
    h = mix32(pack(codes, s.t) * GOLDEN + U32(SALT_MINHASH))
    bins = ((h >> U32(16)) * U32(s.eta)) >> U32(16)
    mh = np.stack([window_min(np.where(bins == U32(j), h, MAX32), w)
                   for j in range(s.eta)], axis=-2)
    # densification: an empty bin takes its next non-empty neighbour's
    # value, offset by the distance (in order of distance, as filled)
    for off in range(1, s.eta):
        donor = np.roll(mh, -off, axis=-2)
        mh = np.where((mh == MAX32) & (donor != MAX32),
                      donor + U32((int(GOLDEN) * off) & 0xFFFFFFFF), mh)
    hi, lo = kmer_halves(codes, s.k)
    rows = []
    for j in range(s.eta):
        window = to_range(mix32(mh[..., j, :] * U32(2 * j + 3)),
                          s.part // s.L)
        local = to_range(hash_pair(hi, lo, SALT_LOCAL + 31 * j), s.L)
        rows.append(window * U32(s.L) + local + U32(j * s.part))
    return np.stack(rows, axis=-2)


def rh_rows(s: Scheme, codes: np.ndarray) -> np.ndarray:
    """(..., eta, n_kmers) rows of every kmer under random hashing."""
    hi, lo = kmer_halves(codes, s.k)
    return np.stack([to_range(hash_pair(hi, lo, SALT_RH + 31 * j), s.part)
                     + U32(j * s.part) for j in range(s.eta)], axis=-2)


def rows_of(s: Scheme, codes: np.ndarray) -> np.ndarray:
    codes = np.asarray(codes, np.uint8)
    if s.name == "idl":
        return idl_rows(s, codes)
    if s.name == "rh":
        return rh_rows(s, codes)
    raise ValueError(f"the reference has no hash scheme {s.name!r}")


# ---------------------------------------------------------------------------
# The archive and its answers.
# ---------------------------------------------------------------------------

class Archive:
    """Every ``(row, file)`` bit the archive sets, as sorted uint64 keys."""

    def __init__(self, cfg: dict, genomes: np.ndarray, first_file: int = 0):
        self.scheme = scheme_of(cfg)
        self.n_files = int(cfg["n_files"])
        keys = []
        for lo in range(0, genomes.shape[0], 256):      # bounded host memory
            block = genomes[lo:lo + 256]
            rows = rows_of(self.scheme, block).astype(np.uint64)
            files = np.arange(first_file + lo, first_file + lo
                              + block.shape[0], dtype=np.uint64)
            keys.append((rows * np.uint64(self.n_files)
                         + files[:, None, None]).reshape(-1))
        self.keys = np.unique(np.concatenate(keys))

    def answers(self, reads, theta: float) -> list[tuple[int, ...]]:
        """The files that answer each read, in increasing order."""
        out = []
        f = np.uint64(self.n_files)
        eta = self.scheme.eta
        for read in reads:
            rows = rows_of(self.scheme, read).astype(np.uint64)  # (eta, n)
            n_kmers = rows.shape[-1]
            need = math.ceil(theta * n_kmers - 1e-9)
            flat = rows.T.reshape(-1)                 # kmer-major
            lo = np.searchsorted(self.keys, flat * f)
            hi = np.searchsorted(self.keys, (flat + np.uint64(1)) * f)
            n_hit = hi - lo
            if need <= 0:
                out.append(tuple(range(self.n_files)))
                continue
            if not n_hit.any():
                out.append(())
                continue
            probe = np.repeat(np.arange(flat.size), n_hit)
            start = np.repeat(lo - np.cumsum(n_hit) + n_hit, n_hit)
            files = (self.keys[start + np.arange(probe.size)] % f
                     ).astype(np.int64)
            kmer = probe // eta
            # a kmer is in a file when all eta of its rows are set there
            kf, per = np.unique(kmer * self.n_files + files,
                                return_counts=True)
            present = kf[per == eta] % self.n_files
            fid, cover = np.unique(present, return_counts=True)
            out.append(tuple(int(x) for x in fid[cover >= need]))
        return out
