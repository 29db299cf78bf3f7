"""Plain CountSketch for the reference: scatter-add in, gather + median out.

The sketch's hash family is part of the configuration (a sum of tables is
only a table when every party hashes alike), so the coordinate -> (bucket,
sign) map below restates the published layout of the system's banded sketch
as plain integer arithmetic: one block scramble shared by all rows, a
per-row riffle, chunks of ``m`` coordinates hashing their offsets into a
window of ``band * stride`` buckets. It is written from that description in
numpy alone; the table itself is then built the textbook way (``bincount``)
and read the textbook way (gather, sign, median over rows), with none of the
system's matmul layout. Host numpy on purpose: scatter is the slow path of
the chip, and the host has the cores idle once the window has closed.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from threading import Lock

import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)
BLOCK = 1 << 22   # coordinates handled at a time: bounds every temporary
THREADS = 8       # numpy releases the interpreter lock inside these passes


def _ceil_mult(x, q):
    return -(-x // q) * q


def _mix32(x, key):
    with np.errstate(over="ignore"):
        x = (x ^ np.uint32(key)).astype(np.uint32)
        x ^= x >> np.uint32(16)
        x *= _M1
        x ^= x >> np.uint32(13)
        x *= _M2
        x ^= x >> np.uint32(16)
    return x


def _is_prime(n):
    if n < 2:
        return False
    i = 2
    while i * i <= n:
        if n % i == 0:
            return False
        i += 1
    return True


def _next_prime_geq(n):
    n = max(n, 2)
    while not _is_prime(n):
        n += 1
    return n


def riffle_factors(d, m, r):
    """One riffle factor per row, all distinct, with distinct padded lattice
    spacings ``ceil(nc0 / f)``; row 0 is the plain layout (f = 1)."""
    nc0 = max(1, -(-d // m))

    def lattice(f):
        return -(-nc0 // f)

    def pick(target, fs, used_g):
        f = _next_prime_geq(max(2, target))
        for _ in range(10_000):
            if f not in fs and lattice(f) not in used_g:
                return f
            f = _next_prime_geq(f + 1)
            if lattice(f) <= 1 and 1 in used_g:
                break
        f = _next_prime_geq(max(2, target))
        while f in fs:
            f = _next_prime_geq(f + 1)
        return f

    fs = [1]
    used_g = {lattice(1)}
    if r == 1:
        return tuple(fs)
    if nc0 >= m:
        targets = [max(2, int(round(m ** 0.5)))]
        g = 2
        for _ in range(2, r):
            targets.append(max(2, nc0 // g))
            g = _next_prime_geq(g + 1)
    else:
        targets = [max(2, int(round(nc0 ** (row / max(r - 1, 1)))))
                   for row in range(1, r)]
    for t in targets:
        if 0.5 < (m * t) / d < 1.0:
            t = nc0
        f = pick(t, fs, used_g)
        fs.append(f)
        used_g.add(lattice(f))
    return tuple(fs)


class Sketch:
    """Geometry and hashes of an ``r`` x ``c`` sketch of ``d`` coordinates.
    ``seed`` keys every hash. ``cols[row]`` / ``signs[row]`` are made once
    and kept: three rounds read each five times."""

    def __init__(self, d, c, r, seed, band=16):
        self.d, self.c, self.r, self.seed, self.band = d, c, r, int(seed), band
        m = 512
        while m < 32768 and d / m > c / 256:
            m *= 2
        self.m = m = min(m, _ceil_mult(d, 8))
        self.block = b = min(64, max(8, m // 64))
        self.d_eff = _ceil_mult(d, b)
        self.factors = riffle_factors(d, m, r)
        self.rows = []
        for f in self.factors:
            L = _ceil_mult(self.d_eff, m * f)
            nc = L // m
            u = max(1, min(band or 1, nc))
            raw = max(1, round(c / (nc + u - 1)))
            s = max(8, round(raw / 8) * 8)
            self.rows.append(dict(f=f, L=L, nc=nc, u=u, s=s, V=u * s,
                                  width=(nc + u - 1) * s))
        self.c_actual = max(row["width"] for row in self.rows)
        self._maps = None

    def _row_key(self, row):
        x = (row ^ self.seed) & 0xFFFFFFFF
        for _ in range(2):
            x = ((x ^ (x >> 16)) * int(_M1)) & 0xFFFFFFFF
        return np.uint32(x ^ int(_GOLDEN))

    def _block_scramble(self):
        """Where each ``block`` of coordinates lands in scrambled space."""
        nb = self.d_eff // self.block
        key = np.uint32((self.seed * 2654435761) & 0xFFFFFFFF)
        x = _mix32(np.arange(nb, dtype=np.uint32), key)
        order = np.argsort(x, kind="stable").astype(np.uint32)
        inv = np.empty_like(order)
        inv[order] = np.arange(nb, dtype=np.uint32)
        return inv

    def _map_block(self, inv, cols, signs, lo, hi):
        """Buckets and signs of coordinates ``[lo, hi)`` in every row, in
        32-bit arithmetic throughout (a row is far shorter than 2**31)."""
        b = np.uint32(self.block)
        idx = np.arange(lo, hi, dtype=np.uint32)
        spos = inv[idx // b] * b + idx % b
        for row, g in enumerate(self.rows):
            f, m = np.uint32(g["f"]), np.uint32(self.m)
            G = np.uint32(g["L"] // g["f"])
            pos = (spos % G) * f + spos // G
            key = self._row_key(row)
            h = _mix32(pos % m, key) % np.uint32(g["V"])
            cols[row][lo:hi] = (pos // m) * np.uint32(g["s"]) + h
            bits = _mix32(spos, key ^ _GOLDEN) & np.uint32(1)
            signs[row][lo:hi] = 1 - 2 * bits.astype(np.int8)

    def _blocks(self):
        return [(lo, min(lo + BLOCK, self.d)) for lo in range(0, self.d, BLOCK)]

    def _each_block(self, fn):
        with ThreadPoolExecutor(THREADS) as ex:
            return list(ex.map(lambda lh: fn(*lh), self._blocks()))

    def maps(self):
        """``(cols [r, d] int32, signs [r, d] int8)``, made once and kept."""
        if self._maps is None:
            cols = np.empty((self.r, self.d), np.int32)
            signs = np.empty((self.r, self.d), np.int8)
            inv = self._block_scramble()
            self._each_block(lambda lo, hi: self._map_block(inv, cols, signs, lo, hi))
            self._maps = (cols, signs)
        return self._maps

    def zeros(self):
        return np.zeros((self.r, self.c_actual), np.float32)

    def sketch(self, v):
        """Table of a dense ``[d]`` vector (float64 sums, rounded once)."""
        v = np.asarray(v, np.float32)
        cols, signs = self.maps()

        acc, lock = np.zeros((self.r, self.c_actual), np.float64), Lock()

        def one(lo, hi):
            part = [np.bincount(cols[r, lo:hi], weights=v[lo:hi] * signs[r, lo:hi],
                                minlength=self.c_actual) for r in range(self.r)]
            with lock:
                for r in range(self.r):
                    acc[r] += part[r]

        self._each_block(one)
        return acc.astype(np.float32)

    def sketch_sparse(self, idx, vals):
        out = np.zeros((self.r, self.c_actual), np.float64)
        cols, signs = self.maps()
        for row in range(self.r):
            np.add.at(out[row], cols[row, idx], vals * signs[row, idx])
        return out.astype(np.float32)

    def estimate(self, table):
        """Median over rows of each coordinate's signed bucket."""
        cols, signs = self.maps()
        out = np.empty(self.d, np.float32)

        def one(lo, hi):
            out[lo:hi] = median_rows([table[r][cols[r, lo:hi]] * signs[r, lo:hi]
                                      for r in range(self.r)])

        self._each_block(one)
        return out


def median_rows(ests):
    """Exact median over a short odd stack, without the [r, d] sort."""
    if len(ests) != 5:
        return np.median(np.stack(ests), axis=0)
    a, b, c, d, e = ests
    mn, mx = np.minimum, np.maximum
    a, b = mn(a, b), mx(a, b)
    c, d = mn(c, d), mx(c, d)
    a, c = mn(a, c), mx(a, c)
    b, d = mn(b, d), mx(b, d)
    b, c = mn(b, c), mx(b, c)
    return mx(b, mn(c, e))


def top_k_dense(v, k):
    """``v`` with all but its ``k`` largest magnitudes zeroed."""
    mag = np.abs(v)
    if k >= v.size:
        return v.copy()
    kth = np.partition(mag, v.size - k)[v.size - k]
    return np.where((mag >= kth) & (mag > 0), v, np.float32(0))
