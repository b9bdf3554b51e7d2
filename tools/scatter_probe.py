"""HLL register scatter-max experiments (round 5, VERDICT next #1).

The numeric-HLL scatter is the dominant term in the 1B x 50 compute
model (~145 M elem/s measured in r4 across every XLA formulation —
docs/PERF.md).  This probe measures Pallas kernel variants against the
XLA scatter on the chip:

- each timed sample runs K data-dependent repetitions of the op inside
  one jitted call (the register carry makes them sequential), then one
  scalar fetch forces completion; the fetch's round trip is amortized
  over K ops and subtracted via a null-op baseline.

Mosaic constraints discovered here (and encoded in the variants):
- BlockSpec index maps must return i32: under x64 (deequ_tpu enables
  it) a literal 0 traces as i64 and Mosaic fails to legalize the
  index-map func.return;
- scalar stores into VMEM refs are unsupported ("Cannot store scalars
  to VMEM") -> the register file lives in an SMEM output (64 KB);
- scalar LOADS from VMEM blocks are unsupported too -> inputs stream
  as SMEM blocks (small chunks, grid-pipelined DMA).

Run:  python tools/scatter_probe.py [--b 21] [--reps 8] [--iters 3]

Production-shape mode (``--prod``): the fused-scan shape the engine
actually dispatches — C=40 columns x B=2^21 rows x M=2^14 registers —
timed as the STACKED scatter (one flat XLA scatter-max, exactly
sketches/hll.registers_from_hash_pair_stacked's formulation) against
the wired (C, G)-grid Pallas kernel (sketches/pallas_scatter.py, the
same code ``config.pallas_scatter`` enables). Emits one
machine-parseable line prefixed ``PROD_JSON:`` so the flag's default
can be justified from an artifact instead of a doc table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)  # run from a source checkout without installing

import jax
import jax.numpy as jnp
import numpy as np

from deequ_tpu.sketches.hll import M, P

B_LOG2_DEFAULT = 21


def xla_scatter(regs, idx, rho):
    return jnp.maximum(regs, jnp.zeros(M, jnp.int32).at[idx].max(rho))


def make_pallas_two_stream(b_log2: int, chunk_log2: int, skip_cold: bool):
    """idx and rho as separate SMEM streams; registers in SMEM out."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = 1 << b_log2
    CHUNK = 1 << chunk_log2
    G = B // CHUNK

    def kernel(idx_ref, rho_ref, reg_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            def z(i, _):
                reg_ref[0, i] = 0
                return jnp.int32(0)

            jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(M), z, jnp.int32(0)
            )

        def body(i, _):
            r = idx_ref[0, i]
            v = rho_ref[0, i]
            cur = reg_ref[0, r]
            if skip_cold:
                @pl.when(v > cur)
                def _store():
                    reg_ref[0, r] = v
            else:
                reg_ref[0, r] = jnp.maximum(cur, v)
            return jnp.int32(0)

        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(CHUNK), body, jnp.int32(0)
        )

    call = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(
                (1, CHUNK), lambda g: (jnp.int32(0), g), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                (1, CHUNK), lambda g: (jnp.int32(0), g), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, M), lambda g: (jnp.int32(0), jnp.int32(0)), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
    )

    def fn(regs, idx, rho):
        out = call(idx.reshape(1, B), rho.reshape(1, B))
        return jnp.maximum(regs, out.reshape(M))

    return fn


def make_pallas_packed(
    b_log2: int, chunk_log2: int, unroll: int, skip_cold: bool = True
):
    """ONE SMEM stream of (idx << 6 | rho) words: half the SMEM
    traffic and one scalar load per element; unpack with scalar
    shift/mask. ``unroll`` elements per fori iteration to cut loop
    bookkeeping."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = 1 << b_log2
    CHUNK = 1 << chunk_log2
    G = B // CHUNK

    def kernel(packed_ref, reg_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            def z(i, _):
                reg_ref[0, i] = 0
                return jnp.int32(0)

            jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(M), z, jnp.int32(0)
            )

        def body(i, _):
            base = i * jnp.int32(unroll)
            for u in range(unroll):
                w = packed_ref[0, base + u]
                r = jax.lax.shift_right_logical(w, jnp.int32(6))
                v = jnp.bitwise_and(w, jnp.int32(63))
                cur = reg_ref[0, r]

                if skip_cold:
                    @pl.when(v > cur)
                    def _store():
                        reg_ref[0, r] = v
                else:
                    reg_ref[0, r] = jnp.maximum(cur, v)

            return jnp.int32(0)

        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(CHUNK // unroll), body, jnp.int32(0)
        )

    call = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(
                (1, CHUNK), lambda g: (jnp.int32(0), g), memory_space=pltpu.SMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, M), lambda g: (jnp.int32(0), jnp.int32(0)), memory_space=pltpu.SMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
    )

    def fn(regs, idx, rho):
        packed = jnp.bitwise_or(jnp.left_shift(idx, 6), rho)
        out = call(packed.reshape(1, B))
        return jnp.maximum(regs, out.reshape(M))

    return fn


def make_pallas_gmin(b_log2: int, chunk_log2: int, unroll: int):
    """The steady-state gate: registers carry IN (warm from previous
    batches), and the scalar min over them (gmin) lets every element
    with rho <= gmin skip the register load AND store — in steady
    state that is ~1 - 2^-gmin ~ 94% of elements doing only the packed
    load + one compare. gmin refreshes at every chunk boundary whose
    index is a multiple of 16 (cheap: M scalar reads amortized over
    16 * CHUNK elements)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = 1 << b_log2
    CHUNK = 1 << chunk_log2
    G = B // CHUNK

    def kernel(regs_in_ref, packed_ref, reg_ref, gmin_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            def cp(i, acc):
                w = regs_in_ref[0, i]
                reg_ref[0, i] = w
                return jnp.minimum(acc, w)

            g0 = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(M), cp, jnp.int32(127)
            )
            gmin_ref[0] = g0

        @pl.when(
            jnp.logical_and(
                pl.program_id(0) > 0,
                jnp.bitwise_and(
                    pl.program_id(0), jnp.int32(15)
                ) == 0,
            )
        )
        def _refresh():
            def mn(i, acc):
                return jnp.minimum(acc, reg_ref[0, i])

            gmin_ref[0] = jax.lax.fori_loop(
                jnp.int32(0), jnp.int32(M), mn, jnp.int32(127)
            )

        gmin = gmin_ref[0]

        def body(i, _):
            base = i * jnp.int32(unroll)
            for u in range(unroll):
                w = packed_ref[0, base + u]
                v = jnp.bitwise_and(w, jnp.int32(63))

                @pl.when(v > gmin)
                def _hot():
                    r = jax.lax.shift_right_logical(w, jnp.int32(6))
                    cur = reg_ref[0, r]

                    @pl.when(v > cur)
                    def _store():
                        reg_ref[0, r] = v

            return jnp.int32(0)

        jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(CHUNK // unroll), body, jnp.int32(0)
        )

    call = pl.pallas_call(
        kernel,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(
                (1, M),
                lambda g: (jnp.int32(0), jnp.int32(0)),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (1, CHUNK), lambda g: (jnp.int32(0), g),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, M),
            lambda g: (jnp.int32(0), jnp.int32(0)),
            memory_space=pltpu.SMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
    )

    def fn(regs, idx, rho):
        packed = jnp.bitwise_or(jnp.left_shift(idx, 6), rho)
        out = call(regs.reshape(1, M), packed.reshape(1, B))
        return out.reshape(M)

    return fn


def chained(fn, reps):
    """K data-dependent applications per dispatch: the carry makes the
    ops sequential so wall ~= K * op + one round trip."""

    @jax.jit
    def run(regs, idx, rho):
        def step(k, acc):
            # vary the input per step so XLA cannot CSE the chain:
            # rotate indices by a step-dependent offset (stays in
            # [0,M)); keep everything i32 — an int64 input stream
            # breaks the SMEM kernels (x64 is on)
            i2 = jnp.bitwise_and(
                idx + k.astype(jnp.int32), jnp.int32(M - 1)
            )
            return fn(acc, i2, rho)

        return jax.lax.fori_loop(0, reps, step, regs)

    return run


def fetch_forced(run, args, iters):
    out = run(*args)
    _ = int(jnp.max(out))  # warm: compile + first exec
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = run(*args)
        _ = int(jnp.max(out))
        samples.append(time.perf_counter() - t0)
    return min(samples)


def xla_scatter_stacked(regs, idx, rho):
    """(C, B) -> (C, M) via the flat stacked scatter-max — the exact
    XLA formulation of hll.registers_from_hash_pair_stacked."""
    n_cols = idx.shape[0]
    col_ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    flat = (col_ids * M + idx).ravel()
    return jnp.maximum(
        regs,
        jnp.zeros(n_cols * M, jnp.int32)
        .at[flat]
        .max(rho.ravel())
        .reshape(n_cols, M),
    )


def make_pallas_stacked():
    """The PRODUCTION kernel: sketches/pallas_scatter's (C, G)-grid
    unroll-16 packed variant — what config.pallas_scatter wires in."""
    from deequ_tpu.sketches import pallas_scatter as ps

    def fn(regs, idx, rho):
        out = ps._scatter_max_call(idx, rho, M, ps._interpret_forced())
        return jnp.maximum(regs, out)

    return fn


def prod_mode(args) -> None:
    """C=40 x B=2^b x M production shape; prints a PROD_JSON line."""
    C, B = args.cols, 1 << args.b
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, M, (C, B), dtype=np.int32))
    rho = jnp.asarray(
        np.minimum(rng.geometric(0.5, (C, B)).astype(np.int32), 33)
    )
    regs0 = jnp.zeros((C, M), jnp.int32)
    idx_same = jnp.zeros((C, B), jnp.int32)

    print(f"prod shape: C={C}, B=2^{args.b}, M={M}, reps={args.reps}")
    null = chained(lambda r, i, v: jnp.maximum(r, 0), args.reps)
    rt = fetch_forced(null, (regs0, idx, rho), args.iters)
    print(f"round-trip baseline: {rt * 1e3:.1f} ms")

    record = {
        "mode": "prod",
        "C": C,
        "b_log2": args.b,
        "M": M,
        "reps": args.reps,
        "backend": jax.default_backend(),
        "roundtrip_ms": rt * 1e3,
        "variants": {},
    }
    want = want_same = None
    for name, fn in (
        ("xla_stacked", xla_scatter_stacked),
        ("pallas_stacked_u16", make_pallas_stacked()),
    ):
        try:
            run = chained(fn, args.reps)
            got = np.asarray(run(regs0, idx, rho))
            got_same = np.asarray(run(regs0, idx_same, rho))
            if want is None:
                want, want_same = got, got_same
                ok = True
            else:
                ok = bool(
                    (got == want).all() and (got_same == want_same).all()
                )
            wall = fetch_forced(run, (regs0, idx, rho), args.iters) - rt
            per_op = wall / args.reps
            rate = C * B / per_op / 1e6
            record["variants"][name] = {
                "bit_identical": ok,
                "per_op_ms": per_op * 1e3,
                "m_elem_per_s": rate,
            }
            print(
                f"{name:>24}: {per_op * 1e3:8.2f} ms/op  "
                f"{rate:8.1f} M elem/s  "
                f"[{'OK' if ok else 'WRONG'}]"
            )
        except Exception as e:  # noqa: BLE001 — probe tool
            msg = str(e).splitlines()[0][:160]
            record["variants"][name] = {"error": msg}
            print(f"{name:>24}: FAILED {type(e).__name__}: {msg}")
    xla = record["variants"].get("xla_stacked", {})
    pallas = record["variants"].get("pallas_stacked_u16", {})
    if "per_op_ms" in xla and "per_op_ms" in pallas:
        record["pallas_speedup"] = xla["per_op_ms"] / pallas["per_op_ms"]
    print("PROD_JSON: " + json.dumps(record))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=B_LOG2_DEFAULT)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--chunks", type=str, default="11,13")
    ap.add_argument(
        "--prod",
        action="store_true",
        help="production-shape stacked probe (C x 2^b x M) + JSON line",
    )
    ap.add_argument("--cols", type=int, default=40)
    args = ap.parse_args()

    if args.prod:
        prod_mode(args)
        return

    B = 1 << args.b
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, M, B, dtype=np.int32))
    rho = jnp.asarray(
        np.minimum(
            rng.geometric(0.5, B).astype(np.int32), 33
        )  # real HLL rank distribution: P(rho=k) = 2^-k from k=1
    )
    regs0 = jnp.zeros(M, jnp.int32)
    # adversarial collision input: every element hits ONE register —
    # correctness under maximal aliasing (ordering hazards show here)
    idx_same = jnp.zeros(B, jnp.int32)

    print(f"B=2^{args.b}, M={M} (P={P}), reps={args.reps}")

    null = chained(lambda r, i, v: jnp.maximum(r, 0), args.reps)
    rt = fetch_forced(null, (regs0, idx, rho), args.iters)
    print(f"round-trip baseline: {rt * 1e3:.1f} ms")

    variants = [("xla_scatter", xla_scatter)]
    for chunk in (int(c) for c in args.chunks.split(",")):
        variants.append(
            (f"two_stream_c{chunk}",
             make_pallas_two_stream(args.b, chunk, skip_cold=False))
        )
        variants.append(
            (f"two_stream_skip_c{chunk}",
             make_pallas_two_stream(args.b, chunk, skip_cold=True))
        )
        for unroll in (4, 8, 16):
            variants.append(
                (f"packed_c{chunk}_u{unroll}",
                 make_pallas_packed(args.b, chunk, unroll))
            )
        variants.append(
            (f"packed_c{chunk}_u8_nosk",
             make_pallas_packed(args.b, chunk, 8, skip_cold=False))
        )
        for unroll in (8, 16):
            variants.append(
                (f"gmin_c{chunk}_u{unroll}",
                 make_pallas_gmin(args.b, chunk, unroll))
            )

    want = want_same = None
    for name, fn in variants:
        try:
            run = chained(fn, args.reps)
            got = np.asarray(run(regs0, idx, rho))
            got_same = np.asarray(run(regs0, idx_same, rho))
            if want is None:
                want, want_same = got, got_same
                ok = "ref"
            else:
                ok = (
                    "OK"
                    if (got == want).all() and (got_same == want_same).all()
                    else "WRONG"
                )
            wall = fetch_forced(run, (regs0, idx, rho), args.iters) - rt
            per_op = wall / args.reps
            rate = B / per_op / 1e6
            print(
                f"{name:>24}: {per_op * 1e3:7.2f} ms/op  "
                f"{rate:8.1f} M elem/s  [{ok}]"
            )
        except Exception as e:  # noqa: BLE001 — probe tool
            msg = str(e).splitlines()[0][:120]
            print(f"{name:>24}: FAILED {type(e).__name__}: {msg}")


if __name__ == "__main__":
    main()
