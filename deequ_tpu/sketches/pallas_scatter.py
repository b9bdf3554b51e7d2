"""Pallas scatter-max backend for the HLL register build.

tools/scatter_probe.py measured the XLA register scatter at ~145 M
elem/s across every formulation and found one Pallas variant that
beats it: a single SMEM stream of packed ``idx << 6 | rho`` words,
unroll-16 scalar loop, skip-cold stores (1.1-1.15x at B=2^21,
M=2^14 — docs/PERF.md "Pallas scatter kernel probe"). This module
ports that kernel behind ``config.pallas_scatter`` and generalizes it
to the production shape: C columns scattered per fused-scan step.

Layout constraints (encoded here):

- the register file must live in SMEM (scalar VMEM stores are
  unsupported by Mosaic), and SMEM is small — a flat (C*M,) register
  file for C=40 would need 2.6 MB, so the kernel runs a (C, G) grid
  with ONE (M,) = 64 KB register block per column, revisited across
  the G chunk steps (grid iterates the last dimension fastest);
- blocks are rank 1 over flattened buffers: Mosaic requires the last
  two dims of a rank-2 block to be multiples of (8, 128), which a
  (1, CHUNK) block of a (C, B) array breaks for any C > 1, while a
  rank-1 i32 block only needs to be a multiple of XLA's 1024-element
  tile (a 512-element block was refused on the chip);
- inputs stream as (CHUNK,) SMEM blocks (grid-pipelined DMA).

The dispatch contract: :func:`scatter_max` returns ``None`` when the
flag is off or the backend is not a TPU, and the caller
(sketches/hll.py) then runs the XLA scatter. On a TPU the kernel is
compiled AND run once per process at a small shape (Mosaic failures
surface at compile time, not trace time); a failure there raises
rather than falling back. Set ``DEEQU_TPU_PALLAS_INTERPRET=1`` to run
the kernel through the Pallas interpreter on CPU (slow, but lets the
differential tests exercise the real kernel logic —
tests/test_fastpath_differential.py); tests/test_tpu_compile.py
compiles it for a described v5e chip at the production shape.

Both paths are bit-identical: max is commutative/associative and the
padded tail scatters ``rho=0`` into register 0, a no-op against the
zero-initialized file.
"""

from __future__ import annotations

import functools
import os
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu import config

# packed words streamed per grid step: 32 KB of SMEM at i32, the
# probe's best chunk (c13); shorter batches use the next power of two
CHUNK = 1 << 13
# probe's best unroll: elements per fori iteration
UNROLL = 16
# XLA tiles a rank-1 i32 operand by this many elements on the TPU, and
# Mosaic refuses a rank-1 block that is not a multiple of the tile
TILE = 1024


def _interpret_forced() -> bool:
    return os.environ.get("DEEQU_TPU_PALLAS_INTERPRET", "0") == "1"


@functools.lru_cache(maxsize=None)
def _make_call(cols: int, g: int, chunk: int, unroll: int, m: int,
               interpret: bool):
    """Build the (C, G)-grid packed scatter-max pallas_call over FLAT
    buffers: (cols*g*chunk,) i32 packed words -> (cols*m,) i32
    registers. Rank-1 blocks: a (1, chunk) block of a 2-D (C, B) array
    breaks Mosaic's last-two-dims (8, 128) rule for C > 1, while a 1-D
    block only needs to be a multiple of TILE elements."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(packed_ref, reg_ref):
        # fresh column block: zero the register file before the first
        # chunk lands (the block is revisited for all g of this column)
        @pl.when(pl.program_id(1) == 0)
        def _init():
            def z(i, _):
                reg_ref[i] = jnp.int32(0)
                return jnp.int32(0)

            jax.lax.fori_loop(jnp.int32(0), jnp.int32(m), z, jnp.int32(0))

        def body(i, _):
            base = i * jnp.int32(unroll)
            for u in range(unroll):
                w = packed_ref[base + u]
                r = jax.lax.shift_right_logical(w, jnp.int32(6))
                v = jnp.bitwise_and(w, jnp.int32(63))
                cur = reg_ref[r]

                @pl.when(v > cur)
                def _store():
                    reg_ref[r] = v

            return jnp.int32(0)

        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(chunk // unroll), body, jnp.int32(0)
        )

    return pl.pallas_call(
        kernel,
        grid=(cols, g),
        in_specs=[
            pl.BlockSpec(
                (chunk,), lambda c, gg: (c * g + gg,),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (m,), lambda c, gg: (c,), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((cols * m,), jnp.int32),
        interpret=interpret,
    )


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _scatter_max_call(idx, rho, m: int, interpret: bool):
    """(C, B) i32 idx/rho -> (C, m) i32 registers via the kernel,
    padding B up to a chunk multiple with (idx=0, rho=0) no-ops."""
    if m % TILE:
        raise ValueError(f"pallas scatter needs m % {TILE} == 0, got {m}")
    cols, b = idx.shape
    chunk = max(TILE, min(CHUNK, _pow2_at_least(b)))
    bp = -(-b // chunk) * chunk
    packed = jnp.bitwise_or(
        jnp.left_shift(idx.astype(jnp.int32), 6), rho.astype(jnp.int32)
    )
    if bp != b:
        packed = jnp.pad(packed, ((0, 0), (0, bp - b)))
    call = _make_call(cols, bp // chunk, chunk, UNROLL, m, interpret)
    return call(packed.reshape(-1)).reshape(cols, m)


# probe verdict per interpret mode; populated lazily, reset by tests
_PROBE: Dict[bool, bool] = {}


def _probe(interpret: bool) -> None:
    """Compile and run the kernel once at a small legal shape and check
    its registers; raises on any failure. Compiled ahead of time and
    called on host arrays, so it runs eagerly even mid-trace."""
    cols, b, m = 2, 4 * TILE, TILE
    idx = (np.arange(cols * b, dtype=np.int32) % m).reshape(cols, b)
    rho = np.tile(np.array([[1], [2]], np.int32), (1, b))
    call = (
        jax.jit(functools.partial(_scatter_max_call, m=m,
                                  interpret=interpret))
        .lower(idx, rho)
        .compile()
    )
    out = np.asarray(call(idx, rho))
    want = np.tile(np.array([[1], [2]], np.int32), (1, m))
    if not np.array_equal(out, want):
        raise RuntimeError(
            "pallas scatter probe returned wrong registers on "
            f"{jax.default_backend()}: {out[:, :8].tolist()}"
        )


def available() -> bool:
    """Can the kernel run here? False only off the TPU (without the
    interpret override). On a TPU the kernel is probed once, and a
    kernel that fails to compile or computes wrong registers RAISES:
    ``config.pallas_scatter=True`` never silently runs the XLA scatter
    on the chip."""
    interpret = _interpret_forced()
    hit = _PROBE.get(interpret)
    if hit is not None:
        return hit
    if not interpret and jax.default_backend() != "tpu":
        _PROBE[interpret] = False
        return False
    _probe(interpret)
    _PROBE[interpret] = True
    return True


def enabled() -> bool:
    return bool(config.options().pallas_scatter) and available()


def impl_token() -> str:
    """Static plan fingerprint: which scatter backend a freshly traced
    plan would bake in. Rides the engine plan-cache key (and the
    vectorized HLL group token) so a flag flip retraces instead of
    aliasing a stale compile."""
    return "pallas" if enabled() else "xla"


def scatter_max(idx, rho, m: int):
    """Per-column scatter-max of ``rho`` into ``idx`` buckets:
    (C, B) i32 -> (C, m) i32, or ``None`` when the Pallas path is
    off/unavailable (caller falls back to the XLA scatter). idx must
    be in [0, m), rho in [0, 64) — the HLL builder guarantees both
    (idx is P hash bits, rho <= 33; masked rows map to (0, 0))."""
    if not enabled():
        return None
    return _scatter_max_call(idx, rho, m, _interpret_forced())


def _reset_probe_for_tests() -> None:
    _PROBE.clear()
    _make_call.cache_clear()
