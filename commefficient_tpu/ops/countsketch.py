"""TPU-native Count Sketch — blocked, matmul-based, zero random access.

Re-implements the semantics of the reference's ``csvec`` dependency
(``csvec/csvec.py``, ~350 LoC: ``CSVec.accumulateVec`` ~L120-160, ``__add__``
~L160-180, ``_findAllValues``/``_findHHK`` ~L190-260, ``unSketch`` ~L260-290,
``l2estimate`` ~L290-310) with a hash-family layout chosen FOR the TPU rather
than translated from CUDA.

Why not the classic layout: the reference scatters each coordinate to a
random bucket (``scatter_add``) and gathers random buckets back — on GPUs
those are atomic-add/gather at memory bandwidth, but the TPU is a
contiguous-vector machine with no fast random access at ``[D]`` scale: a
scatter or gather of every coordinate is the slow path. (A k-scale one is
not, on the installed JAX 0.9.0: five 50k-update scatter-adds into
5M-column rows, hashes included, measured 5.4 ms on a v5e, 22 ns an
update — scripts/resketch_probe.py, PR 27. The round-3 figure this
docstring carried, ~24 ms for one 50k scatter into 6.5M, no longer holds.)

Layout (this module, v5 — "banded"):
  * Coordinates are split into CHUNKS of ``m``. Chunk q hashes its
    within-chunk offsets into a WINDOW of ``V = band * stride`` buckets
    starting at ``q * stride`` of the global row, so neighboring chunks'
    windows OVERLAP and each coordinate's collision pool is V (~5k)
    buckets, not a private per-chunk pool. One static one-hot, kept by
    band as ``[m, band, stride]``, realizes a whole row as a single
    ``[nc, m] x [m, V]`` MXU matmul whose result stays ``[nc, band,
    stride]``, followed by ``band`` static shifted adds (overlap-add) — no
    scatter, no gather. Estimation stacks the row's ``band`` shifted views
    of whole ``stride``-wide rows as ``[nc, band, stride]`` and contracts
    both dimensions (the transposed matmul), then median across rows. No
    ``[nc, V]`` array exists on either side: ``stride`` is no multiple of
    the 128-lane tile, and merging the band into a ``band * stride`` minor
    dimension was a lane-by-lane relayout of 319 MB a row at the GPT-2
    geometry (PERF.md section 6, PR 32).
  * Before any row layout, ONE seed-derived static permutation of
    ``scramble_block``-sized coordinate blocks (a cheap row-gather)
    decorrelates parameter structure from chunk structure; each row then
    applies a distinct-prime RIFFLE (the flat order of ``reshape(f,
    L/f).T``) so partner sets differ across rows. A factor under the lane
    tile is realized as two tile-aligned moves (``_riffle``); a larger one
    as the plain transpose.

v3/v4 POSTMORTEM (do not regress to disjoint pools): with per-chunk
PRIVATE pools (v3 riffles only, v4 + scramble), a coordinate can only
collide inside its chunk's ~300 buckets. FetchSGD's error sketch
accumulates STRUCTURED mass (layer-correlated magnitudes, long waits for
small coordinates), and per-chunk collision noise grows with the hot
chunks — the extract-and-subtract feedback loop then amplifies phantom
estimates: measured on ResNet-9 at paper-scale settings (d/c=13, k=d/130,
lr 0.4, momentum 0.9) as exponential divergence (train loss 459 after 6
epochs; NaN under several variants), while an EXACT classic scatter
sketch under identical server algebra converged (acc 0.315). Banding
restores a classic-grade collision scope at MXU cost: the same config
converges at acc 0.340 with band=16 at default matmul precision
(scripts/sketch_lab.py reproduces the whole comparison; forcing
Precision.HIGHEST changes nothing but costs 3x — the divergence was never
a precision problem). Single-shot estimate quality was IDENTICAL across
layouts (recall@k ~0.38 on a real gradient) — only the iterated feedback
loop separates them; test any future layout change with the lab's
multi-epoch run, not one-shot properties.

Linearity is the contract that makes federated aggregation exact:
``sketch(a) + sketch(b) == sketch(a + b)`` (bit-exact in float32 mode up to
float addition order), so ``lax.psum`` of worker tables IS the sketch of the
summed update. Precision caveat: on TPU the matmul paths run at the default
(bf16-pass) matmul precision, so matmul-path results (sketch_vec,
estimate_all) carry ~2^-8 RELATIVE rounding vs the exact gather/scatter
paths (sketch_sparse, estimate_at) — exact on CPU, ~4e-3 relative on TPU.
Training is insensitive (accumulate and EF-subtract share the matmul path,
so the rounding cancels to first order; lab-verified), and forcing
Precision.HIGHEST costs 3x for no accuracy change.

``num_blocks`` (reference: GPU-memory hash-reuse chunking, csvec.py
~L60-100) is here the memory knob for FULL-d estimation: with
``num_blocks > 1``, ``estimate_all`` runs the exact gather path over
``num_blocks`` coordinate slices under ``lax.map``, bounding the transient
to ``r * d/num_blocks`` instead of the matmul path's ``r * d_eff`` stack
(2.5 GB at GPT-2 scale d=124M r=5 — the same scale the reference needs
``numBlocks=20`` at). Semantics are identical (pinned by
test_num_blocks_invariance); speed is lower (gather is the TPU slow path),
which is the same memory-for-speed trade the reference's flag makes.

All functions are pure and jit/vmap/shard_map-friendly.
"""

from __future__ import annotations

import functools as _functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

_M1 = np.uint32(0x85EBCA6B)
_M2 = np.uint32(0xC2B2AE35)
_GOLDEN = np.uint32(0x9E3779B9)

# Mersenne prime for the optional 4-universal polynomial hash family
# ("poly4", the reference csvec's guarantee class, csvec.py ~L10-80).
# 2^31 - 1 keeps every Horner product a*x < 2^62 inside uint64 on the host.
_MERSENNE_P = np.uint64(2**31 - 1)


def _poly4_eval(x: np.ndarray, coeffs: np.ndarray) -> np.ndarray:
    """((c0 x^3 + c1 x^2 + c2 x + c3) mod p) for uint64 x < p — Horner with
    every intermediate < 2^62, exact in uint64. 4-wise independent over the
    seed-random coefficients (degree-3 polynomial over GF(p))."""
    # Exactness (every Horner product < 2^62) AND 4-universality both
    # require inputs inside the field: x < p. A silent wrap here would
    # degrade the guarantee class without failing loudly (ADVICE r3).
    if x.size and int(x.max()) >= int(_MERSENNE_P):
        raise ValueError(
            f"poly4 hash input {int(x.max())} >= p=2^31-1; the 4-universal "
            "family is only defined over GF(p) — use hash_family='fmix32' "
            "at this scale"
        )
    acc = np.zeros_like(x) + coeffs[0]
    for a in coeffs[1:]:
        acc = (acc * x + a) % _MERSENNE_P
    return acc

_P31 = np.uint32(2**31 - 1)  # numpy scalar: embeds as a literal inside
# Pallas kernel bodies (a jnp scalar would be a captured constant that
# pallas_call rejects)


def _fold31(y: jnp.ndarray) -> jnp.ndarray:
    """One Mersenne fold: y (< 2^32) -> congruent value <= 2^31."""
    return (y & _P31) + (y >> jnp.uint32(31))


def _modmul31(a: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(a * x) mod (2^31 - 1), exact, for a, x < 2^31 - 1 — uint32 only.

    TPUs (and Pallas kernel bodies) have no uint64, so the Horner products
    of the poly4 family are evaluated in 16-bit limbs: a*x = H*2^32 +
    M*2^16 + L with H = ah*xh < 2^30, M = ah*xl + al*xh < 2^32, L = al*xl
    < 2^32 (each fits uint32). With 2^31 === 1 (mod p): H*2^32 === 2H, and
    M*2^16 folds as (M >> 15) + ((M & 0x7fff) << 16). Every partial sum is
    folded before it can overflow; the result is reduced to < p, matching
    the host uint64 ``% p`` bit-for-bit (pinned by
    tests/test_countsketch_pallas.py)."""
    u16 = jnp.uint32(16)
    mask16 = jnp.uint32(0xFFFF)
    ah, al = a >> u16, a & mask16
    xh, xl = x >> u16, x & mask16
    H = ah * xh
    M = ah * xl + al * xh
    L = al * xl
    t0 = H << jnp.uint32(1)                                   # < 2^31
    t1 = (M >> jnp.uint32(15)) + ((M & jnp.uint32(0x7FFF)) << u16)
    t1 = _fold31(_fold31(t1))                                 # <= p
    t2 = _fold31(_fold31(L))                                  # <= p
    acc = _fold31(_fold31(t0 + t1))                           # <= p
    acc = _fold31(_fold31(acc + t2))                          # <= p
    return jnp.where(acc >= _P31, acc - _P31, acc)            # < p


def _poly4_u32(x: jnp.ndarray, coeffs) -> jnp.ndarray:
    """Horner evaluation of the seed-derived degree-3 polynomial over
    GF(2^31-1) in uint32 — identical values to the host uint64
    ``_poly4_eval`` for inputs < p. ``coeffs`` are static python ints.
    Safe both in regular jit traces and inside Pallas kernel bodies."""
    acc = jnp.full(x.shape, jnp.uint32(int(coeffs[0])))
    for a in coeffs[1:]:
        acc = _modmul31(acc, x) + jnp.uint32(int(a))          # <= p + p - 1
        acc = _fold31(_fold31(acc))
        acc = jnp.where(acc >= _P31, acc - _P31, acc)
    return acc


def _is_prime(n: int) -> bool:
    if n < 2:
        return False
    i = 2
    while i * i <= n:
        if n % i == 0:
            return False
        i += 1
    return True


def _next_prime_geq(n: int) -> int:
    n = max(n, 2)
    while not _is_prime(n):
        n += 1
    return n


@_functools.lru_cache(maxsize=None)
def _riffle_factors(d: int, m: int, r: int) -> tuple:
    """Per-row riffle factors (always distinct).

    A pair of coordinates at distance delta is co-chunked in row f only
    when ``delta < m/f`` (its "window") or delta lands near a multiple of
    L/f. The median over r rows is corrupted only when >= ceil(r/2) rows
    co-chunk the same pair, so the factor set must keep the number of
    rows whose window covers any given delta BELOW that.

    Strong regime (nc >= m, i.e. d >= ~m^2 — the CV production scales;
    GPT-2's d/c~100 pushes m above sqrt(d) for pool size and lands in the
    small regime with ~330-bucket pools):
    factors are (1, ~sqrt(m), then for row i >= 2 a prime near nc/g_i
    with g_i the i-th odd-indexed prime (2, 3, 5, ...)). Those rows have
    window m/f ~= g_i m^2/d of order one, so near pairs co-chunk in at
    most ~2 rows, AND — critically — their far-pair lattices have
    spacings G = L/f ~= g_i * m that are pairwise DISTINCT (a pair lands
    on >= 2 giant rows' lattices only at lcm-scale spacings). Taking
    consecutive primes >= nc instead makes L = m*f and G = m for EVERY
    giant row — identical far-pair partner sets across rows, the v2
    repeated-partner pathology at lattice scale (measured: |Se| -> 1e9 in
    the fixed-input iteration). mf ~= d also keeps padding ~O(1%).

    Small regime (nc < m): a geometric prime ladder 1..~nc, bumping any
    factor out of the bad padding zone mf in (d/2, d) (where L = 2mf
    nearly doubles the row and halves its bucket pool). Windows can't
    shrink below m/nc > 1 without multi-x padding, so near pairs remain
    co-chunked in several rows; with the >=128 bucket pools this measures
    stable in the FetchSGD feedback iteration, but adversarially tight
    heavy-hitter clusters can still produce phantoms at this scale (the
    strong regime, or an explicit smaller ``m``, avoids them).
    """
    nc0 = max(1, -(-d // m))

    def lattice(f: int) -> int:
        # padded lattice spacing G = L/f in units of m: ceil(nc0/f)
        return -(-nc0 // f)

    def pick(target: int, fs: list, used_g: set) -> int:
        """Smallest prime >= target whose f AND padded lattice spacing G
        are both unused. G-distinctness is the invariant (two rows with
        equal G share their entire far-pair partner lattice — the v2
        repeated-partner pathology at lattice scale; composite/bumped
        factors hit this through padding, e.g. f=5 and f=6 at nc0=10 both
        give G=2m). When every G >= target is exhausted (tiny nc0), fall
        back to a distinct prime with the least-used G."""
        f = _next_prime_geq(max(2, target))
        for _ in range(10_000):
            if f not in fs and lattice(f) not in used_g:
                return f
            f = _next_prime_geq(f + 1)
            if lattice(f) <= 1 and 1 in used_g:
                break  # G saturated at m; no distinct G above here
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
        targets = [
            max(2, int(round(nc0 ** (row / max(r - 1, 1)))))
            for row in range(1, r)
        ]
    for t in targets:
        if 0.5 < (m * t) / d < 1.0:  # bad padding zone: jump past ~nc
            t = nc0
        f = pick(t, fs, used_g)
        fs.append(f)
        used_g.add(lattice(f))
    return tuple(fs)


def _mix32(x: jnp.ndarray, key) -> jnp.ndarray:
    """murmur3 fmix32 with a key fold — uint32 in, well-scrambled uint32 out."""
    x = (x ^ key).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 13)
    x = x * _M2
    x = x ^ (x >> 16)
    return x


class CountSketch(NamedTuple):
    """Static spec of a Count Sketch (the analog of a ``CSVec`` instance).

    The reference couples spec + table + device state in one class; here the
    spec is a hashable static NamedTuple (safe to close over under ``jit``)
    and the table is a plain ``[r, c]`` float array threaded functionally.

    ``c`` is a TARGET column count: each row realizes ``nc_row * s_row``
    columns (rows pad independently for their riffle factors; ``s_row``
    re-targets c per row, clamped to a multiple of 8) and the table width
    ``c_actual`` is the max over rows — within a few percent of the
    request for large d.
    """

    d: int  # length of the vectors being sketched
    c: int  # requested columns (buckets) per row
    r: int  # rows (independent repetitions; median across them)
    num_blocks: int = 1  # >1: chunk estimate_all's memory (module docstring)
    seed: int = 42  # hash seed; equal seeds => equal hashes everywhere
    m: Any = None  # chunk size (coords per bucket block); None = adaptive
    dtype: Any = jnp.float32  # matmul dtype (measured: no v5e speed delta)
    # Global block-scramble (v4). REAL gradients have correlated
    # neighborhoods (a conv kernel's coords sit contiguously in the flat
    # vector with comparable magnitudes). Riffles alone cannot separate
    # pairs closer than m/nc, so a whole correlated cluster co-chunks in
    # most rows and collides inside the tiny per-chunk bucket pool with
    # prob ~cluster/s PER ROW — the median breaks and FetchSGD's feedback
    # loop amplifies the corruption (measured: ResNet-9 training diverges,
    # loss 459 after 6 epochs, while a classic scatter sketch on identical
    # server algebra converges). One static seed-derived permutation of
    # ``scramble_block``-sized blocks, shared by all rows and applied
    # before the per-row riffle/chunk layout, scatters any contiguous
    # cluster uniformly over the chunks: residual same-chunk cluster mass
    # drops from ~cluster/s to ~block/s in >=3 rows simultaneously with
    # probability ~(block/s)^3 — classic-grade. Cost: one [nb, block]
    # row-gather per sketch/estimate — and the ROW SIZE of that gather is
    # the sketch path's measured hot spot (r4, v5e, d=6.5M/c=500k: whole
    # sketch_vec 14.9 ms at block=8 vs 7.9 ms at block=64; estimate_all
    # 21.8 -> 15.2 ms — 8-float rows are a worst case for the TPU gather
    # engine, 64-float rows ~2x faster end-to-end). block=64 keeps the
    # splitting property comfortably: (block/s)^3 at the headline
    # geometry (s=312) is ~0.9% per cluster, and the r4 stability checks
    # (quarter-scale lab, full-scale 7x357k accuracy run, adversarial
    # structured-input tests) hold at 64 — see CHANGELOG_r4. BUT a block
    # must stay small relative to the CHUNK, or a tied contiguous cluster
    # rides one block into one chunk and corrupts the median (the
    # adversarial equal-magnitude test catches exactly this at lab m=64),
    # so None (default) resolves adaptively via ``sblock``:
    # min(64, max(8, chunk_m // 64)) — 64 at production chunk sizes
    # (m=4096 CV, m=8192+ GPT-2), back to 8 at small-m lab geometries.
    # Explicit int pins it; 0 disables (pre-v4 layout).
    scramble_block: Optional[int] = None

    @property
    def sblock(self) -> int:
        """Realized scramble block (see scramble_block field note)."""
        if self.scramble_block is not None:
            # ADVICE r4: a stray non-int (e.g. a float from a config sweep)
            # would flow through sblock/d_eff layout arithmetic unchecked
            # and corrupt the geometry silently — reject it here.
            if (
                not isinstance(self.scramble_block, (int, np.integer))
                or isinstance(self.scramble_block, bool)
            ):
                raise TypeError(
                    "scramble_block must be an int (got "
                    f"{self.scramble_block!r}); it is layout arithmetic, "
                    "not a tunable float"
                )
            return int(self.scramble_block)
        return min(64, max(8, self.chunk_m // 64))
    # Banded buckets (v5). With disjoint per-chunk pools, a coordinate can
    # only ever collide inside its chunk's s (~300) buckets; FetchSGD's
    # error sketch accumulates STRUCTURED mass and the feedback loop
    # measurably diverges at paper-scale d/c even after the scramble and
    # full-f32 matmuls, while a classic (global-bucket) scatter sketch
    # converges under identical server algebra. Banding interpolates the
    # two at MXU cost: chunk q hashes its offsets into a WINDOW of
    # V = band * stride buckets starting at q * stride, so windows of
    # neighboring chunks overlap and each coordinate's collision pool
    # grows 16-64x while the row stays ONE [nc, m] x [m, V] einsum plus
    # ``band`` static shifted adds (overlap-add; no scatter, no gather).
    # band=1 reproduces the disjoint-pool v4 layout; cost scales ~linearly
    # with band (still sub-ms per row at CV scale).
    band: int = 16
    # Hash family for the offset-slot and sign hashes. "fmix32" (default,
    # production): stateless murmur fmix32 — empirically validated
    # (uniformity/decorrelation tests + the multi-epoch lab) but with no
    # independence guarantee. "poly4": seed-derived degree-3 polynomials
    # over GF(2^31 - 1) — the 4-universal guarantee class of the
    # reference's csvec (~L10-80), provided as the lab A/B backstop
    # (VERDICT r2 item 7) so any suspected hash pathology can be tested
    # against a provable family. Scale note: the EINSUM backend's matmul
    # path materializes the [d_eff] poly4 sign vector host-side (fine at
    # CV scale, prohibitive at D=124M); the PALLAS backend evaluates the
    # polynomial in-kernel over uint32 GF(2^31-1) arithmetic (_poly4_u32)
    # and the gather path (_row_cols_signs) does the same on the fly, so
    # backend="pallas" makes poly4 a production-scale family.
    hash_family: str = "fmix32"
    # Kernel backend for the MATMUL-path entry points — sketch_vec,
    # estimate_all's full-d path, and everything built on them
    # (sketch_add_vec, unsketch, unsketch_dense, the round's server
    # algebra). "einsum" (default): the banded [m, V] one-hot einsum +
    # overlap-add above. "pallas": tiled Pallas TPU kernels
    # (ops/pallas/countsketch_kernels.py) that generate the one-hot, the
    # signs, and the band overlap-add INSIDE the kernel — no materialized
    # [m, V] one-hot constant, no [nc, V] window round-trip, no [d_eff]
    # sign vector; interpret mode on CPU, Mosaic on TPU. The two backends
    # share one geometry/hash mapping and agree to fp32 rounding (float
    # summation order differs; pinned by tests/test_countsketch_pallas).
    # Gather/scatter-path ops (sketch_sparse, estimate_at, num_blocks>1
    # estimation) are not matmul-bound and stay backend-agnostic.
    backend: str = "einsum"
    # STORAGE dtype of the [r, c_actual] table (distinct from ``dtype``,
    # the matmul OPERAND dtype). float32 (default): bit-exact tables, the
    # r1-r5 production path — every golden recording pins it. bfloat16:
    # tables are stored/psummed/carried in bf16 while every accumulation
    # (the in-row einsum/kernel reductions, the server momentum/error
    # algebra) stays f32 — halving table HBM traffic and the device_encode
    # psum's collective bytes at GPT-2 scale ([5, 5M] table: 100 MB -> 50
    # MB per round per link). bf16 shares f32's exponent range (no
    # overflow risk), so the cost is ~2^-8 relative rounding at each
    # downcast; the LINEAR aggregation contract (compress/) then holds to
    # that tolerance instead of bit-exactly (pinned by
    # tests/test_countsketch_bf16.py). Estimation upcasts to f32 on read.
    table_dtype: Any = jnp.float32

    # -- derived static geometry ------------------------------------------
    @property
    def d_eff(self) -> int:
        """Scrambled-space length: d padded to a block multiple."""
        b = self.sblock
        return _ceil_mult(self.d, b) if b else self.d

    @property
    def chunk_m(self) -> int:
        """Chunk size. Adaptive default: grow m (512..32768, powers of 2)
        until each chunk gets >= 256 buckets.

        Measured alternative when the floor binds (r5, runs/r5_sketch5.log
        + r5_r7probe.log): at r=7 x c=357k the floor forces m=8192/s=432
        and a 1.42x-wide einsum window per row; pinning ``m=4096``
        (s=224, just under the floor) with ``band=24`` (restores the
        overlap-add collision pool to V ~ 5184) trains to 0.9004 vs the
        default geometry's 0.8997 at 25% less wall-clock. Do NOT go
        further down: m=2048 (s=112) diverges — the floor is a real
        stability boundary, band is the safe recovery lever.

        The bucket-pool target is STABILITY-critical, not a tuning nicety:
        with small pools the per-chunk victim sets are so small that
        FetchSGD's extract-and-subtract feedback loop amplifies collision
        noise instead of damping it. Measured on the fixed-input
        iteration at d=6.6M, c=d/13 (t=59 |Se|max; classic scatter sketch
        = 1526): s=40 -> 2.8e13, s=80 -> 8.7e6, s=160 -> 6981, s=312 ->
        1812, s=624 -> 1680. s~256+ is classic-equivalent; the adaptive
        rule targets that. The larger m also keeps the per-chunk floor of
        8 from inflating the realized table at large d/c (the cap bounds
        the [m, s] one-hot operand at ~40 MB). NB the d/c RATIO itself has
        a measured stability envelope independent of this geometry: the
        r3 lab measured d/c<=25 stable and d/c>=50 diverging for EVERY
        layout tried (banded, global pools, classic scatter, poly4) —
        FetchSGD-style virtual-error feedback runs out of SNR, so GPT-2
        scale needs c >= D/25 (FederatedSession warns; CHANGELOG_r3)."""
        if self.m is not None:
            return min(self.m, _ceil_mult(self.d, 8))
        m = 512
        while m < 32768 and self.d / m > self.c / 256:
            m *= 2
        return min(m, _ceil_mult(self.d, 8))

    @property
    def nc(self) -> int:
        # chunk count of the LARGEST row (each row pads independently so
        # its riffle factor divides its padded length)
        return max(self._nc_row(r) for r in range(self.r))

    def _factor(self, row: int) -> int:
        return _riffle_factors(self.d, self.chunk_m, self.r)[row]

    def _L_row(self, row: int) -> int:
        """Per-row padded length: smallest multiple of m * factor >= d_eff
        (the scrambled-space length the row layouts actually operate on)."""
        return _ceil_mult(self.d_eff, self.chunk_m * self._factor(row))

    def _nc_row(self, row: int) -> int:
        return self._L_row(row) // self.chunk_m

    def u_row(self, row: int) -> int:
        """Band width (windows per chunk) for this row, capped by nc.

        Band width does NOT rescue the d/c~100 regime: the r3 lab measured
        band=16 and global windows (band >= nc, pool = half the row)
        diverging IDENTICALLY at quarter scale (loss ~2e17 by epoch 12,
        fmix32 and poly4 alike, lr 0.04 and 0.08 alike) — see the
        hash_family note and CHANGELOG_r3 for the regime account."""
        return max(1, min(self.band or 1, self._nc_row(row)))

    def s_row(self, row: int) -> int:
        """Bucket STRIDE per chunk for THIS row: chunk q's window starts at
        ``q * s_row``; the realized row width is (nc + u - 1) * s_row,
        targeted at the requested c. (Per-row, so a heavily padded row
        must not shrink every other row's bucket pool.)"""
        raw = max(1, round(self.c / (self._nc_row(row) + self.u_row(row) - 1)))
        return max(8, round(raw / 8) * 8)  # nearest multiple of 8

    def V_row(self, row: int) -> int:
        """Bucket-pool (window) size per chunk: band * stride."""
        return self.u_row(row) * self.s_row(row)

    @property
    def s(self) -> int:
        return self.s_row(0)

    @property
    def c_actual(self) -> int:
        return max(
            (self._nc_row(r) + self.u_row(r) - 1) * self.s_row(r)
            for r in range(self.r)
        )

    @property
    def table_shape(self) -> tuple[int, int]:
        return (self.r, self.c_actual)

    def empty(self, dtype=None) -> jnp.ndarray:
        """A zeroed sketch table (``CSVec.zero()`` analog, csvec.py ~L110).
        Allocated in ``table_dtype`` unless overridden."""
        return jnp.zeros(
            self.table_shape, dtype=self.table_dtype if dtype is None else dtype
        )

    # -- per-row hash ingredients (all static-shape, derived from seed) ----
    def _row_key(self, row: int) -> np.uint32:
        x = (row ^ self.seed) & 0xFFFFFFFF
        for _ in range(2):
            x = ((x ^ (x >> 16)) * int(_M1)) & 0xFFFFFFFF
        return np.uint32(x ^ int(_GOLDEN))

    def _poly4_coeffs(self, row: int, purpose: int) -> np.ndarray:
        """[4] uint64 in [1, p): seed-derived coefficients for this row's
        degree-3 hash polynomial (purpose 0 = bucket slots, 1 = signs)."""
        # host rng at TRACE time, on purpose: SeedSequence((spec.seed,
        # row, purpose)) is a pure function of the sketch spec, so every
        # trace bakes the SAME coefficient table — replay/retrace-safe
        # by construction (pinned by the golden parity recordings).
        # lint: allow[traced-purity] seed-derived trace-time constants
        rng = np.random.default_rng(
            np.random.SeedSequence([int(self.seed) & 0x7FFFFFFF, row, purpose])
        )
        return rng.integers(1, int(_MERSENNE_P), size=4).astype(np.uint64)

    def _row_signs(self, row: int) -> jnp.ndarray:
        """[d_eff] ±1, hashed from the SCRAMBLED-space index (v4: sketching
        happens in scrambled space; ``_row_cols_signs`` maps an original
        coordinate to its scrambled position before hashing, so all entry
        points agree)."""
        if self.hash_family == "poly4":
            idx = np.arange(self.d_eff, dtype=np.uint64)
            bits = _poly4_eval(idx, self._poly4_coeffs(row, 1)) & np.uint64(1)
            return jnp.asarray(1.0 - 2.0 * bits.astype(np.float32))
        idx = jnp.arange(self.d_eff, dtype=jnp.uint32)
        bits = _mix32(idx, self._row_key(row) ^ _GOLDEN) & jnp.uint32(1)
        return 1.0 - 2.0 * bits.astype(jnp.float32)

    def _offset_slots(self, row: int) -> jnp.ndarray:
        """[m] int32 in-window bucket per within-chunk offset (shared by all
        chunks; chunk q's window starts at ``q * s_row``)."""
        if self.hash_family == "poly4":
            off = np.arange(self.chunk_m, dtype=np.uint64)
            slots = _poly4_eval(off, self._poly4_coeffs(row, 0)) % np.uint64(
                self.V_row(row)
            )
            return jnp.asarray(slots.astype(np.int32))
        off = jnp.arange(self.chunk_m, dtype=jnp.uint32)
        return (
            _mix32(off, self._row_key(row)) % jnp.uint32(self.V_row(row))
        ).astype(jnp.int32)


@_functools.lru_cache(maxsize=None)
def _scramble_perms(d_eff: int, block: int, seed: int):
    """(sperm, inv_sperm) over the d_eff/block blocks: output block j of the
    scramble reads input block sperm[j]; input block B lands at output
    position inv_sperm[B]. Seed-derived (equal seeds => equal scramble on
    every host/device, like the hashes)."""
    nb = d_eff // block
    # pure numpy (callable under an active jax trace): same fmix32 rounds
    key = np.uint32((seed * 2654435761) & 0xFFFFFFFF)
    x = np.arange(nb, dtype=np.uint32) ^ key
    with np.errstate(over="ignore"):
        x ^= x >> np.uint32(16)
        x *= _M1
        x ^= x >> np.uint32(13)
        x *= _M2
        x ^= x >> np.uint32(16)
    sperm = np.argsort(x, kind="stable").astype(np.int32)
    inv = np.empty_like(sperm)
    inv[sperm] = np.arange(nb, dtype=np.int32)
    return sperm, inv


def _median_rows(ests: jnp.ndarray) -> jnp.ndarray:
    """Median over axis 0 of an [r, d] stack — min/max selection networks
    for the common small odd r (r=3: 3 ops; r=5: 7 ops), else jnp.median.

    jnp.median lowers to a full XLA sort: measured 4.9 ms net for [5, 6.5M]
    on v5e where the 5-element network costs 2.8 ms (r4 perf probe). The
    networks return exactly the middle element, bit-equal to jnp.median
    for odd r (pinned by tests)."""
    mn, mx = jnp.minimum, jnp.maximum
    r = ests.shape[0]
    if r == 1:
        return ests[0]
    if r == 3:
        a, b, c = ests[0], ests[1], ests[2]
        return mx(mn(a, b), mn(mx(a, b), c))
    if r == 5:
        a, b, c, d, e = ests[0], ests[1], ests[2], ests[3], ests[4]
        a, b = mn(a, b), mx(a, b)
        c, d = mn(c, d), mx(c, d)
        a, c = mn(a, c), mx(a, c)  # a: min of {a,b,c,d}
        b, d = mn(b, d), mx(b, d)  # d: max of {a,b,c,d}
        b, c = mn(b, c), mx(b, c)  # median(all) = median of {b, c, e}
        return mx(b, mn(c, e))
    return jnp.median(ests, axis=0)


def _scramble(spec: "CountSketch", v: jnp.ndarray) -> jnp.ndarray:
    """[d] -> [d_eff] scrambled (block-permuted) vector."""
    b = spec.sblock
    if not b:
        return v
    sperm, _ = _scramble_perms(spec.d_eff, b, spec.seed)
    vp = jnp.pad(v, (0, spec.d_eff - spec.d))
    return vp.reshape(-1, b)[jnp.asarray(sperm)].reshape(spec.d_eff)


def _unscramble(spec: "CountSketch", v_s: jnp.ndarray) -> jnp.ndarray:
    """[d_eff] scrambled -> [d] original order."""
    b = spec.sblock
    if not b:
        return v_s[: spec.d]
    _, inv = _scramble_perms(spec.d_eff, b, spec.seed)
    return v_s.reshape(-1, b)[jnp.asarray(inv)].reshape(spec.d_eff)[: spec.d]


def _riffle_tile(spec: "CountSketch", row: int) -> int:
    """Lane tile ``t`` of this row's riffle, or 0 for the plain
    ``reshape(f, G).T`` form. The tiled moves are for a factor shorter than
    a lane tile: the plain transpose then leaves ``G`` rows of ``f < 128``
    floats, each a partial tile, and the re-pitch to the chunk size runs row
    by row (f = 97 at the GPT-2 geometry: 28.0 ms to the layout and 17.4 ms
    back against 8.9 and 9.0 tiled; the large primes' plain form reads 12-13
    ms either way and keeps it — scripts/sketch_layout_probe.py, PR 32). They
    need a chunk size that has the tile."""
    return 128 if 1 < spec._factor(row) < 128 and spec.chunk_m % 128 == 0 else 0


def _riffle(x: jnp.ndarray, f: int, t: int) -> jnp.ndarray:
    """[L] position order -> [L] riffled order (``x.reshape(f, G).T`` flat)
    as two tile-aligned transposes. ``G = L/f`` is a multiple of the chunk
    size by construction (``_L_row``), so with ``a = a1 * t + a2`` the
    riffled index ``a * f + b`` reads ``a1 * (t f) + (a2 * f + b)``: the
    prime ``f`` is only ever the major part of a minor dimension ``t f``,
    never a minor dimension itself (XLA:TPU keeps the two moves apart: a
    ``copy``, a row merge, a ``copy``, the re-cut to the chunk size; the
    one transpose with an ``f``-wide minor dimension compiles to a
    ``while`` of row-granular ``dynamic-update-slice``s — PERF.md section
    6, PR 32)."""
    A = x.shape[0] // (f * t)
    y = x.reshape(f, A, t).transpose(2, 0, 1)  # [a2, b, a1]
    return y.reshape(t * f, A).T.reshape(-1)  # [a1, a2 f + b]


def _unriffle(x: jnp.ndarray, f: int, t: int) -> jnp.ndarray:
    """Inverse of ``_riffle``: the same two moves backwards."""
    A = x.shape[0] // (f * t)
    y = x.reshape(A, t * f).T  # [a2 f + b, a1]
    return y.reshape(t, f, A).transpose(1, 2, 0).reshape(-1)  # [b, a1, a2]


def _to_layout(spec: "CountSketch", x_d: jnp.ndarray, row: int) -> jnp.ndarray:
    """[d] position-ordered -> [nc_row, m] chunk layout for this row.

    Riffle with factor f: original coordinate p lands at riffled index
    ``(p mod G) * f + p // G`` with ``G = L_row / f`` — the flat order of
    ``reshape(f, G).T``, realized as tiled moves (``_riffle``) where
    ``_riffle_tile`` says so. Chunks are then contiguous blocks of m. f=1
    rows are plain contiguous chunking.
    """
    f, L = spec._factor(row), spec._L_row(row)
    xp = jnp.pad(x_d, (0, L - spec.d_eff))
    t = _riffle_tile(spec, row)
    if t:
        xp = _riffle(xp, f, t)
    elif f > 1:
        xp = xp.reshape(f, L // f).T.reshape(L)
    return xp.reshape(L // spec.chunk_m, spec.chunk_m)


def _from_layout(spec: "CountSketch", x_chunks: jnp.ndarray, row: int) -> jnp.ndarray:
    """[nc_row, m] chunk layout -> [d] position-ordered (inverse)."""
    f, L = spec._factor(row), spec._L_row(row)
    xp = x_chunks.reshape(L)
    t = _riffle_tile(spec, row)
    if t:
        xp = _unriffle(xp, f, t)
    elif f > 1 and spec.chunk_m % 128 == 0:
        # the plain transpose written onto the vector's own [f, G/128, 128]
        # tiles: its result is the flat vector bit for bit, where the
        # [f, G] form pays one more pass to re-tile it (estimate_all at
        # the GPT-2 geometry 96.7 -> 87.6 ms; the same view on the way in
        # makes the re-pitch loop transpose as well and loses, 86.0 ->
        # 88.2 ms for sketch_vec — scripts/sketch_layout_probe.py, PR 32)
        xp = xp.reshape(L // f // 128, 128, f).transpose(2, 0, 1).reshape(L)
    elif f > 1:
        xp = xp.reshape(L // f, f).T.reshape(L)
    return xp[: spec.d_eff]


def _ceil_mult(x: int, q: int) -> int:
    return -(-x // q) * q


def _band_onehot(spec: CountSketch, row: int) -> jnp.ndarray:
    """The row's static one-hot by band: ``[m, u, s]``, window ``i`` of a
    chunk being the ``s`` buckets from ``(q + i) * s`` of the flat row. The
    products contract ``(u, s)`` or leave it as two dimensions, so no
    ``[nc, V]`` array with the band merged into a ``u * s`` minor dimension
    (328 is no multiple of 128: that merge was a lane-by-lane relayout of
    319 MB a row) exists on either side."""
    u, s = spec.u_row(row), spec.s_row(row)
    buckets = jnp.arange(u * s, dtype=jnp.int32).reshape(u, s)
    return (spec._offset_slots(row)[:, None, None] == buckets).astype(spec.dtype)


def _sketch_one_row(spec: CountSketch, v_s: jnp.ndarray, row: int) -> jnp.ndarray:
    # v_s is already in scrambled space ([d_eff]); signs are scrambled-keyed
    sv = _to_layout(spec, v_s * spec._row_signs(row), row).astype(spec.dtype)
    # NB matmul precision: the default (fast bf16-pass) path measures
    # STABLE in the FetchSGD feedback loop once the banded layout is in
    # place (lab acc 0.340 at paper-scale settings, vs 0.305 with
    # Precision.HIGHEST at 3x the matmul cost) — the one-hot operand is
    # exact in bf16 and the ~2^-8 relative bucket noise is far below the
    # collision noise floor. The divergence postmortem (module docstring)
    # was a LAYOUT problem, not a precision problem.
    u = spec.u_row(row)
    out = jnp.einsum(
        "cm,mus->cus",
        sv,
        _band_onehot(spec, row),
        preferred_element_type=jnp.float32,
    )
    if u == 1:
        out = out.reshape(-1)
    else:
        # overlap-add: window i of chunk q lands on row q + i of the
        # [nc + u - 1, s] row view — u statically shifted padded slices
        # summed in one reduction (a sequential .at[i:i+nc].add chain
        # serialized u dynamic-update-slices)
        out = sum(
            jnp.pad(out[:, i, :], ((i, u - 1 - i), (0, 0))) for i in range(u)
        ).reshape(-1)
    return jnp.pad(out, (0, spec.c_actual - out.shape[0]))


def _use_pallas(spec: CountSketch) -> bool:
    """Backend dispatch for the matmul-path ops (see the ``backend`` field
    note). Centralized so an unknown backend fails loudly at every entry."""
    b = spec.backend
    if b not in ("einsum", "pallas"):
        raise ValueError(
            f"CountSketch.backend must be 'einsum' or 'pallas', got {b!r}"
        )
    return b == "pallas"


def sketch_vec(spec: CountSketch, v: jnp.ndarray) -> jnp.ndarray:
    """Sketch a dense [d] vector into an [r, c_actual] table.

    Equivalent of ``CSVec.accumulateVec`` (csvec.py ~L120-160) applied to a
    fresh table. Linear: ``sketch_vec(a+b) == sketch_vec(a)+sketch_vec(b)``
    (the scramble and layouts are fixed permutations, the matmul is linear).
    ``spec.backend`` picks the kernel realization; the table is the same to
    fp32 rounding either way.
    """
    if _use_pallas(spec):
        from commefficient_tpu.ops.pallas import sketch_vec_pallas

        return sketch_vec_pallas(spec, v)
    v = _scramble(spec, v.astype(jnp.float32))  # ONE block-gather, all rows
    table = jnp.stack([_sketch_one_row(spec, v, r) for r in range(spec.r)])
    # rows accumulate in f32 (preferred_element_type above); only the
    # FINAL table downcasts to the storage dtype (a no-op for the f32
    # default — convert_element_type to the same dtype folds away)
    return table.astype(spec.table_dtype)


def sketch_add_vec(spec: CountSketch, table: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """``table += sketch(v)`` — the in-place accumulate of the reference,
    expressed functionally (csvec.py ``accumulateVec`` ~L120-160)."""
    return table + sketch_vec(spec, v)


def table_sqnorm_estimate(table: jnp.ndarray) -> jnp.ndarray:
    """AMS estimate of ``||v||^2`` from v's CountSketch table [r, c]: each
    row's squared norm is an unbiased estimate of ``||v||^2`` (signs are
    4-universal), and the median over rows tames collision outliers — the
    classic AMS/CountSketch F2 estimator. Free relative to an unsketch: no
    estimate pass, no [d] transient. Used by the telemetry diagnostics
    (sketch-mode norm scalars, the replicated AND FSDP rounds). The f32
    upcast matters for bf16-stored tables: a bf16 sum-of-squares would lose
    the estimate to accumulation rounding (a no-op for the f32 default)."""
    return jnp.median(jnp.sum(jnp.square(table.astype(jnp.float32)), axis=1))


def _estimate_one_row(spec: CountSketch, table_row: jnp.ndarray, row: int) -> jnp.ndarray:
    nc, u, t = spec._nc_row(row), spec.u_row(row), spec.s_row(row)
    acc = table_row[: (nc + u - 1) * t].reshape(nc + u - 1, t).astype(spec.dtype)
    # chunk q's windows are rows q .. q + u - 1 of the row view: whole rows
    # of s buckets, stacked as [nc, u, s] and contracted as two dimensions
    win = jnp.stack([acc[i : i + nc] for i in range(u)], axis=1)
    est = jnp.einsum(
        "cus,mus->cm",
        win,
        _band_onehot(spec, row),
        preferred_element_type=jnp.float32,
    )
    # scrambled-space estimate [d_eff]; estimate_all unscrambles after the
    # median so the block-gather happens once, not once per row
    return _from_layout(spec, est, row) * spec._row_signs(row)


def estimate_all(spec: CountSketch, table: jnp.ndarray) -> jnp.ndarray:
    """Median-of-rows estimates for ALL d coordinates.

    ``CSVec._findAllValues`` analog (csvec.py ~L190-260): per row, gather
    each coordinate's bucket value times sign (here: transposed matmul),
    then median across the r estimates (in scrambled space), then ONE
    block-gather back to original coordinate order.

    ``num_blocks > 1`` switches to the memory-bounded path: the exact
    gather estimate (``estimate_at``) over ``num_blocks`` coordinate
    slices, sequenced by ``lax.map`` so only one slice's ``[r, d/B]``
    transient is live at a time (vs the matmul path's full ``[r, d_eff]``
    stack). Same values (one-hot matmul sums exactly one term per
    coordinate, so the two paths agree to float rounding; bit-equal on
    CPU), lower peak memory, slower — the reference ``numBlocks`` trade.

    ``spec.backend`` picks the kernel realization of the full-d matmul
    path (einsum | pallas); the num_blocks gather path is backend-agnostic.
    """
    use_pallas = _use_pallas(spec)  # validate the backend string even on
    # the gather path below — every entry point fails loudly on a typo
    # named_scope marker (no ops added): the scope name survives into the
    # compiled HLO's op metadata, so tests can pin that a lowered program
    # contains NO full-d estimate — the sharded-decode acceptance
    # criterion (tests/test_sketch_decode.py's HLO pin)
    with jax.named_scope("estimate_all"):
        if spec.num_blocks > 1:
            B = spec.num_blocks
            blk = -(-spec.d // B)
            idx = jnp.arange(B * blk, dtype=jnp.uint32).reshape(B, blk)
            idx = jnp.minimum(idx, jnp.uint32(spec.d - 1))  # pad: repeat last
            est = jax.lax.map(lambda ix: estimate_at(spec, table, ix), idx)
            return est.reshape(B * blk)[: spec.d]
        if use_pallas:
            from commefficient_tpu.ops.pallas import estimate_all_pallas

            return estimate_all_pallas(spec, table)
        ests = jnp.stack(
            [_estimate_one_row(spec, table[r], r) for r in range(spec.r)]
        )
        # the barrier keeps the unscramble's [blocks, block] view out of the
        # rows: without it XLA:TPU moves that reshape above the median and
        # every row pays a reshape, a slice and a layout copy of its own
        # [d_eff] estimates (three passes over 0.5 GB a row) to be laid out
        # for a gather that runs once
        return _unscramble(spec, jax.lax.optimization_barrier(_median_rows(ests)))


def _scrambled_pos(spec: CountSketch, idx: jnp.ndarray) -> jnp.ndarray:
    """Original coordinate index -> its position in scrambled space."""
    b = spec.sblock
    if not b:
        return idx
    _, inv = _scramble_perms(spec.d_eff, b, spec.seed)
    inv = jnp.asarray(inv).astype(jnp.uint32)
    return inv[(idx // jnp.uint32(b)).astype(jnp.int32)] * jnp.uint32(b) + (
        idx % jnp.uint32(b)
    )


def _row_cols_signs(spec: CountSketch, idx: jnp.ndarray, row: int):
    """(column index, sign) of each ORIGINAL coordinate in ``idx`` for one
    row — the gather/scatter-side view of the same mapping ``sketch_vec``
    realizes with scramble + riffle + chunk layout + one-hot matmul."""
    idx = idx.astype(jnp.uint32)
    spos = _scrambled_pos(spec, idx)
    f, L = spec._factor(row), spec._L_row(row)
    G = jnp.uint32(L // f)
    # riffled index of scrambled position p: (p mod G) * f + p // G
    pos = (spos % G) * jnp.uint32(f) + spos // G
    chunk = (pos // jnp.uint32(spec.chunk_m)).astype(jnp.int32)
    off = pos % jnp.uint32(spec.chunk_m)
    s_r = spec.s_row(row)
    if spec.hash_family == "poly4":
        # slots gather from the [m] static table (host polynomial — m is
        # bounded at any scale); signs are evaluated ON THE FLY over
        # GF(2^31-1) in uint32 (_poly4_u32 — bit-identical to the host
        # uint64 path), so the gather path never materializes a [d_eff]
        # sign vector either and poly4 stays usable at GPT-2 scale.
        if spec.d_eff >= int(_MERSENNE_P):
            raise ValueError(
                f"poly4 scrambled-space length {spec.d_eff} >= p=2^31-1; "
                "the 4-universal family is only defined over GF(p) — use "
                "hash_family='fmix32' at this scale"
            )
        h = spec._offset_slots(row)[off.astype(jnp.int32)]
        bits = _poly4_u32(
            spos, tuple(int(c) for c in spec._poly4_coeffs(row, 1))
        ) & jnp.uint32(1)
        sign = 1.0 - 2.0 * bits.astype(jnp.float32)
        return chunk * s_r + h, sign
    h = (
        _mix32(off, spec._row_key(row)) % jnp.uint32(spec.V_row(row))
    ).astype(jnp.int32)
    # signs are keyed by the SCRAMBLED position (applied pre-layout in
    # _sketch_one_row), slots by the within-chunk offset
    bits = _mix32(spos, spec._row_key(row) ^ _GOLDEN) & jnp.uint32(1)
    sign = 1.0 - 2.0 * bits.astype(jnp.float32)
    return chunk * s_r + h, sign


def estimate_at(spec: CountSketch, table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Median-of-rows point estimates for a subset of coordinates
    (``CSVec._findValues`` analog, csvec.py ~L190-230). Small-k gather path."""

    def one_row(row: int):
        cols, sign = _row_cols_signs(spec, idx, row)
        # explicit f32 read: bf16-stored tables estimate in f32 (no-op
        # for the f32 default)
        return table[row, cols].astype(jnp.float32) * sign

    ests = jnp.stack([one_row(r) for r in range(spec.r)])
    return _median_rows(ests)


def sketch_sparse(spec: CountSketch, idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Sketch a k-sparse vector given as (indices [k], values [k]).

    Same hash mapping as ``sketch_vec`` of the dense materialization (see
    ``_row_cols_signs``) via O(r·k) scatter-adds — bit-identical on CPU;
    on TPU the dense path's matmul carries ~2^-8 relative rounding (module
    docstring precision caveat) while this is exact float32 (2.4e-7 from
    the float64 sums on table entries up to 4.8). Measured on a v5e at
    d=124M, 5 x 5M, k=50k (scripts/resketch_probe.py, PR 27): 5.4 ms, the
    hashes 2.2 of it, against 100.6 ms for ``sketch_vec`` of the same
    k-sparse vector and 18.0 ms for the same table as five one-hot
    ``[c/2048, k] x [k, 2048]`` MXU matmuls — so a top-k's pairs are
    sketched here, and ``sketch_vec`` is for vectors dense at ``[d]``
    scale. Coordinates may repeat; repeats accumulate.
    """
    vals = vals.astype(jnp.float32)

    def one_row(row: int):
        cols, sign = _row_cols_signs(spec, idx, row)
        return jnp.zeros((spec.c_actual,), jnp.float32).at[cols].add(vals * sign)

    return jnp.stack([one_row(r) for r in range(spec.r)])


def sketch_segment(spec: CountSketch, offset: int, vals: jnp.ndarray) -> jnp.ndarray:
    """Sketch the contiguous flat-[d] segment ``[offset, offset + n)``
    given its values (any shape; raveled) — the per-leaf building block of
    the sketch-fused backward. ``offset`` is STATIC (a python int: each
    param leaf's position in the ``ravel_pytree`` layout). Same hash
    mapping as ``sketch_sparse`` at ``idx = offset + arange(n)``, so by
    linearity the sum of every leaf's segment sketch IS the sketch of the
    full flat gradient — without the [d] concat ever existing."""
    flat = vals.reshape(-1).astype(jnp.float32)
    idx = jnp.uint32(int(offset)) + jnp.arange(flat.shape[0], dtype=jnp.uint32)
    return sketch_sparse(spec, idx, flat)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def sketch_grad_tap(spec: CountSketch, offset: int, leaf, table):
    """Identity on ``leaf`` whose TRANSPOSE sketches the leaf's cotangent.

    The sketch-fused backward (parallel/round.py make_sketch_grad_one):
    thread every param leaf through a tap that shares one dummy zeros
    ``table`` [r, c_actual] f32, then differentiate the loss w.r.t. that
    table — each tap's backward rule emits
    ``sketch_segment(spec, offset, dL/dleaf)`` as the table's cotangent,
    JAX's fan-in accumulation sums them, and the result is the sketch of
    the full flat gradient. The per-leaf cotangents are consumed where AD
    produces them; ``ravel_pytree``'s flat [D] concat (the transpose of
    ``unravel``) is never traced because the params vector itself is not
    differentiated. Forward is the identity on ``leaf`` (the zeros table
    contributes nothing), so the loss value is untouched."""
    del table
    return leaf


def _sketch_grad_tap_fwd(spec, offset, leaf, table):
    del table
    return leaf, None


def _sketch_grad_tap_bwd(spec, offset, _res, ct):
    # leaf cotangent passes through untouched (correct if a caller also
    # differentiates the params; unused -> DCE'd); the table cotangent is
    # this leaf's segment sketch
    return ct, sketch_segment(spec, offset, ct)


sketch_grad_tap.defvjp(_sketch_grad_tap_fwd, _sketch_grad_tap_bwd)


def unsketch_sparse(
    spec: CountSketch, table: jnp.ndarray, k: int, *, approx: bool = False
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Recover the top-k heavy hitters as (indices [k], values [k]).

    ``CSVec.unSketch`` analog (csvec.py ~L260-290): median estimates for all
    coordinates, then global top-k by magnitude. ``approx=True`` uses
    ``lax.approx_max_k`` (TPU-native, faster, ~0.95 recall) — callers opt in.
    """
    est = estimate_all(spec, table)
    with jax.named_scope("topk_select"):  # telemetry.trace.ROUND_SCOPES
        if approx:
            _, hh_idx = jax.lax.approx_max_k(jnp.abs(est), k)
        else:
            _, hh_idx = jax.lax.top_k(jnp.abs(est), k)
        return hh_idx, est[hh_idx]


def unsketch(
    spec: CountSketch, table: jnp.ndarray, k: int, *, approx: bool = False
) -> jnp.ndarray:
    """``unsketch_sparse`` materialized as a dense [d] vector, k nonzeros."""
    hh_idx, vals = unsketch_sparse(spec, table, k, approx=approx)
    with jax.named_scope("topk_select"):
        return jnp.zeros(spec.d, dtype=vals.dtype).at[hh_idx].set(vals)


def unsketch_dense(spec: CountSketch, table: jnp.ndarray, k: int) -> jnp.ndarray:
    """Top-k heavy hitters as a dense [d] vector via THRESHOLD selection —
    no sort, no scatter (both are slow on TPU; see ops.topk).

    Same contract as ``unsketch`` except selection is by a binary-searched
    magnitude threshold, so the nonzero count is ≤ k (ties at the threshold
    are dropped rather than arbitrarily broken — at most a handful of
    coordinates on float gradients).
    """
    from commefficient_tpu.ops.topk import topk_threshold_dense

    est = estimate_all(spec, table)
    return topk_threshold_dense(est, k)


def l2_estimate(spec: CountSketch, table: jnp.ndarray) -> jnp.ndarray:
    """Estimate of the L2 norm of the sketched vector: median of row norms
    (``CSVec.l2estimate``, csvec.py ~L290-310)."""
    return jnp.median(jnp.linalg.norm(table.astype(jnp.float32), axis=1))
