"""HLL register build on the MXU: a factored one-hot matmul with banded
power-of-two ranks.

A register is the MAX rank over the rows that hash to it; a matmul only
ADDS. The max survives the sum when each rank is encoded as a power of
two far enough from its neighbours that no count of smaller terms can
reach the next one:

- Inside a tile of at most 2^12 rows, a row of rank ``rho`` in band
  ``k`` (ranks ``9k+1 .. 9k+9``) contributes ``2^(13·level)``, level =
  ``rho - 9k - 1`` in 0..8. Every value is a power of two, so it is
  exact in bf16 (2^104 at most, under bf16's 2^127).
- The f32 sum S of one register's terms in one band lies in
  ``[2^(13m), 2^(13m+13))`` where m is the largest level present: the
  lower bound because adding non-negative terms never rounds below the
  largest term; the upper bound because at most 2^12 terms are each at
  most 2^(13m), and the f32 accumulation error (≤ 2^12 · 2^-24
  relative) cannot reach 2^(13m+13). So m is the number of j in 1..8
  with ``S >= 2^(13j)``, read exactly.
- Four bands cover ranks 1..36, every rank the HLL produces (1..33).
  Rank 0 marks a masked row or padding and contributes nothing.
- The register index factors as ``idx = LO·hi + lo``. One tile's
  register block is then ONE matmul: the (BANDS·HI, T) banded one-hot of
  ``hi`` against the (LO, T) one-hot of ``lo``, contracted over the T
  rows, gives the (BANDS·HI, LO) band sums; the max over bands (a higher
  band always outranks a lower one) is the (HI, LO) register block.

The registers are BIT-identical to the scatter-max's (the decode is
exact), so states, mesh merges and ``STATE_FORMAT_VERSIONS`` are
untouched. :func:`registers` is the Pallas TPU kernel (one register
block per column in VMEM, revisited across the row tiles; the operands
exist only in VMEM); :func:`reference_registers` is the same math in
plain jnp, the tests' second witness. ``DEEQU_TPU_PALLAS_INTERPRET=1``
runs the kernel through the Pallas interpreter on the CPU
(tests/test_sketches.py); tests/test_tpu_compile.py compiles it for a
described v5e chip.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

LO_BITS = 7
LO = 1 << LO_BITS  # one-hot width of the low index bits (MXU lanes)
BAND = 9  # ranks per band
BANDS = 4  # 4 x 9 = 36 >= 33, the largest HLL rank
SPACING = 13  # exponent step between levels: 2^12 terms never carry over
TILE = 1 << 12  # rows summed before a decode (the exactness bound)
SUB = 128  # rows per MXU dot: one lane row of the (TILE/128, 128) block
MIN_TILE = 1024  # an i32 block of (8, 128), Mosaic's smallest


def _encode(idx, rho, hi_count: int):
    """(register index, rank) -> (banded row key, low index, bf16-exact
    band value). Rank 0 gets key -1: it matches no row."""
    hi = idx >> LO_BITS
    lo = idx & (LO - 1)
    band = (
        (rho > BAND).astype(jnp.int32)
        + (rho > 2 * BAND).astype(jnp.int32)
        + (rho > 3 * BAND).astype(jnp.int32)
    )
    level = rho - 1 - BAND * band
    key = jnp.where(rho > 0, band * hi_count + hi, jnp.int32(-1))
    val = jax.lax.bitcast_convert_type(
        jnp.left_shift(level * SPACING + 127, 23), jnp.float32
    )
    return key, lo, val


def _decode(sums, hi_count: int):
    """(..., BANDS·HI, LO) f32 band sums -> (..., HI, LO) i32 ranks."""
    out = None
    for k in range(BANDS):
        s = sums[..., k * hi_count:(k + 1) * hi_count, :]
        rank = jnp.full(s.shape, BAND * k + 1, jnp.int32)
        for j in range(1, BAND):  # the level: how many 2^(13j) S reaches
            rank = rank + (s >= jnp.float32(2.0 ** (SPACING * j))).astype(
                jnp.int32
            )
        rank = jnp.where(s > 0, rank, jnp.int32(0))
        out = rank if out is None else jnp.maximum(out, rank)
    return out


def _interpret_forced() -> bool:
    return os.environ.get("DEEQU_TPU_PALLAS_INTERPRET", "0") == "1"


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _tile_for(b: int) -> int:
    return max(MIN_TILE, min(TILE, _pow2_at_least(b)))


@functools.lru_cache(maxsize=None)
def _make_call(cols: int, g: int, tile: int, m: int, interpret: bool):
    """The (C, G)-grid pallas_call: (C, G·tile/128, 128) i32 packed
    ``idx << 6 | rho`` words -> (C, m/LO, LO) i32 registers."""
    import jax.experimental.pallas as pl

    hi_count = m // LO
    rows = tile // SUB

    def kernel(w_ref, reg_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            reg_ref[...] = jnp.zeros(reg_ref.shape, jnp.int32)

        w = w_ref[...]
        key, lo, val = _encode(
            jax.lax.shift_right_logical(w, jnp.int32(6)),
            jnp.bitwise_and(w, jnp.int32(63)),
            hi_count,
        )
        key_iota = jax.lax.broadcasted_iota(
            jnp.int32, (BANDS * hi_count, SUB), 0
        )
        lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LO, SUB), 0)
        sums = jnp.zeros((BANDS * hi_count, LO), jnp.float32)
        for r in range(rows):
            banded = jnp.where(
                key_iota == key[r:r + 1, :], val[r:r + 1, :], jnp.float32(0)
            ).astype(jnp.bfloat16)
            onehot = jnp.where(
                lo_iota == lo[r:r + 1, :], jnp.float32(1), jnp.float32(0)
            ).astype(jnp.bfloat16)
            sums = sums + jax.lax.dot_general(
                banded, onehot, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        reg_ref[...] = jnp.maximum(reg_ref[...], _decode(sums, hi_count))

    return pl.pallas_call(
        kernel,
        grid=(cols, g),
        in_specs=[
            pl.BlockSpec(
                (None, rows, SUB), lambda c, t: (c, t, jnp.int32(0))
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, hi_count, LO),
            lambda c, t: (c, jnp.int32(0), jnp.int32(0)),
        ),
        out_shape=jax.ShapeDtypeStruct((cols, hi_count, LO), jnp.int32),
        interpret=interpret,
    )


def _registers_call(idx, rho, m: int, interpret: bool):
    cols, b = idx.shape
    tile = _tile_for(b)
    bp = max(1, -(-b // tile)) * tile  # one tile at least: B may be 0
    packed = jnp.bitwise_or(
        jnp.left_shift(idx.astype(jnp.int32), 6), rho.astype(jnp.int32)
    )
    if bp != b:  # (idx 0, rank 0) words add nothing
        packed = jnp.pad(packed, ((0, 0), (0, bp - b)))
    call = _make_call(cols, bp // tile, tile, m, interpret)
    return call(packed.reshape(cols, bp // SUB, SUB)).reshape(cols, m)


def registers(idx, rho, m: int):
    """(C, B) i32 register indices in [0, m) and ranks in [0, 33] ->
    (C, m) i32 registers through the Pallas kernel (interpreted under
    ``DEEQU_TPU_PALLAS_INTERPRET=1``)."""
    return _registers_call(idx, rho, m, _interpret_forced())


def reference_registers(idx, rho, m: int):
    """The kernel's math in plain jnp, one tile at a time (``lax.map``,
    so no (C, B, BANDS·HI) operand is ever whole)."""
    cols, b = idx.shape
    hi_count = m // LO
    tile = _tile_for(b)
    bp = max(1, -(-b // tile)) * tile  # one tile at least: B may be 0
    pad = ((0, 0), (0, bp - b))
    idx = jnp.pad(idx.astype(jnp.int32), pad)
    rho = jnp.pad(rho.astype(jnp.int32), pad)

    def one_tile(args):
        i, r = args  # (C, tile)
        key, lo, val = _encode(i, r, hi_count)
        banded = jnp.where(
            key[:, :, None] == jnp.arange(BANDS * hi_count),
            val[:, :, None], 0.0,
        ).astype(jnp.bfloat16)
        onehot = (lo[:, :, None] == jnp.arange(LO)).astype(jnp.bfloat16)
        sums = jnp.einsum(
            "ctk,ctl->ckl", banded, onehot,
            preferred_element_type=jnp.float32,
        )
        return _decode(sums, hi_count)

    tiles = (
        idx.reshape(cols, -1, tile).transpose(1, 0, 2),
        rho.reshape(cols, -1, tile).transpose(1, 0, 2),
    )
    blocks = jax.lax.map(one_tile, tiles)  # (G, C, HI, LO)
    return blocks.max(axis=0).reshape(cols, m)


def _probe() -> None:
    """Compile and run the kernel once on the chip at one tile and check
    it against a numpy scatter-max, including the exactness bound: a
    full tile of top-of-band ranks in one register. Raises on a wrong
    register."""
    m = 1 << 14
    rng = np.random.default_rng(0)
    idx = rng.integers(0, m, (2, TILE), dtype=np.int32)
    rho = rng.integers(0, 34, (2, TILE), dtype=np.int32)
    idx[1], rho[1] = 5, BAND  # 2^12 terms of 2^104: the bound itself
    call = (
        jax.jit(functools.partial(_registers_call, m=m, interpret=False))
        .lower(idx, rho)
        .compile()
    )
    got = np.asarray(call(idx, rho))
    want = np.zeros((2, m), np.int32)
    for c in range(2):
        np.maximum.at(want[c], idx[c], rho[c])
    if not np.array_equal(got, want):
        bad = np.argwhere(got != want)[:4].tolist()
        raise RuntimeError(
            f"MXU register build disagrees with the scatter-max on "
            f"{jax.default_backend()} at {bad}"
        )


@functools.lru_cache(maxsize=None)
def available() -> bool:
    """The MXU build runs on a TPU. Probed once per process; a kernel
    that fails to compile or computes a wrong register RAISES, never
    falls back in silence."""
    if jax.default_backend() != "tpu":
        return False
    _probe()
    return True
