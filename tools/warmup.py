"""Precompile deequ_tpu's fused plans for a schema, ahead of data.

First-EVER XLA:TPU compilation of a big fused profiler plan costs
~110 s (20-col plan; docs/PERF.md pool 3). The persistent cache
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``)
makes it one-time per machine — but without this tool, the FIRST
production run eats it in full. Run warmup at deploy time instead:

    python tools/warmup.py --like-parquet /path/to/table.parquet
    python tools/warmup.py --schema '{"price": "float32", "id": "int64",
                                      "cat": "string"}'

and the first production run's compiles become ~0.1-2 s cache
deserializations (measured; docs/PERF.md).

What gets compiled is keyed by (analyzer structure, schema kinds,
batch shape, wire dtypes) — NOT by data values (dictionaries/LUTs ride
as runtime inputs). The synthetic warm data therefore only has to hit
the same STATIC decisions production data will:

- batch size (``--batch-size``, default = the engine default);
- per-column wire dtype: int64 columns whose values all fit int32
  ship narrowed, so ``--int-width`` picks which program to warm
  (``both`` warms the two variants);
- null presence: an all-valid column's mask is synthesized on device
  (a DIFFERENT program than a shipped mask), so ``--nullable both``
  (default) warms both.

``--suite`` additionally warms a VerificationSuite-shaped plan
(completeness/uniqueness/compliance per column) on top of the default
ColumnProfiler plan.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import numpy as np  # noqa: E402

_KINDS = (
    "float32", "float64", "int32", "int64", "string", "bool", "timestamp"
)


def _schema_from_parquet(path: str):
    import pyarrow.dataset as pads
    import pyarrow as pa

    schema = pads.dataset(path, format="parquet").schema
    out = {}
    for name, typ in zip(schema.names, schema.types):
        if pa.types.is_dictionary(typ):
            typ = typ.value_type
        if pa.types.is_floating(typ):
            out[name] = "float32" if typ.bit_width == 32 else "float64"
        elif pa.types.is_boolean(typ):
            out[name] = "bool"
        elif pa.types.is_integer(typ):
            out[name] = "int32" if typ.bit_width <= 32 else "int64"
        elif pa.types.is_string(typ) or pa.types.is_large_string(typ):
            out[name] = "string"
        elif pa.types.is_timestamp(typ) or pa.types.is_date(typ):
            out[name] = "timestamp"
        else:
            print(f"  (skipping unsupported column {name}: {typ})")
    return out


def synthetic_dataset(schema, rows: int, nullable: bool, wide_ints: bool,
                      seed: int = 0, high_card_strings: bool = False):
    """A dataset matching the schema's STATIC compile decisions.
    ``high_card_strings`` warms the i32-codes / no-histogram program
    (dictionary-code wire width and the profiler's low-cardinality
    histogram gate are both static per column)."""
    import pyarrow as pa

    from deequ_tpu.data import Dataset

    rng = np.random.default_rng(seed)
    cols = {}
    null_mask = (
        (rng.random(rows) < 0.05) if nullable else np.zeros(rows, bool)
    )
    for name, kind in schema.items():
        if kind in ("float32", "float64"):
            vals = rng.normal(0.0, 1.0, rows).astype(kind)
            arr = pa.array(vals, mask=null_mask if nullable else None)
        elif kind in ("int32", "int64"):
            hi = (1 << 40) if (wide_ints and kind == "int64") else 1 << 20
            vals = rng.integers(0, hi, rows).astype(kind)
            arr = pa.array(vals, mask=null_mask if nullable else None)
        elif kind == "bool":
            arr = pa.array(
                rng.random(rows) < 0.5,
                mask=null_mask if nullable else None,
            )
        elif kind == "timestamp":
            base = np.datetime64("2024-01-01", "us")
            vals = base + rng.integers(0, 1 << 40, rows).astype(
                "timedelta64[us]"
            )
            arr = pa.array(vals, pa.timestamp("us"),
                           mask=null_mask if nullable else None)
        elif kind == "string":
            # 64 distinct -> i8 codes + the profiler's histogram pass;
            # 200k distinct -> i32 codes, histogram gate off
            n_cats = min(200_000, max(rows, 2)) if high_card_strings else 64
            cats = np.array([f"w{j:06d}" for j in range(n_cats)])
            vals = cats[rng.integers(0, len(cats), rows)]
            arr = pa.array(
                vals, mask=null_mask if nullable else None
            ).dictionary_encode()
        else:
            raise ValueError(f"unknown kind {kind!r} (use one of {_KINDS})")
        cols[name] = arr
    return Dataset.from_arrow(pa.table(cols))


def warm_once(schema, rows, nullable, wide_ints, suite: bool,
              high_card_strings: bool = False, checks=None,
              profile: bool = True, engine=None) -> float:
    """One warm pass: the ColumnProfiler plan (unless ``profile=False``)
    plus a VerificationSuite plan — either the EXACT production
    ``checks`` (the service warms the suites it will actually serve) or
    a synthesized schema-shaped check when ``suite=True``. ``engine``
    pins a specific ``AnalysisEngine`` (e.g. a mesh over an elastic
    device slice) so the pass warms THAT placement shape's plan."""
    ds = synthetic_dataset(
        schema, rows, nullable, wide_ints,
        high_card_strings=high_card_strings,
    )
    t0 = time.time()
    if profile:
        from deequ_tpu.profiles.profiler import ColumnProfiler

        ColumnProfiler.profile(ds, engine=engine)
    if checks is not None:
        from deequ_tpu import VerificationSuite

        # compiles key on structure/shapes/dtypes, never values — a
        # synthetic dataset with the production schema warms the
        # production suite's plan exactly
        VerificationSuite().on_data(ds).add_checks(
            list(checks)
        ).with_engine(engine).run()
    elif suite:
        from deequ_tpu import Check, CheckLevel, VerificationSuite

        check = Check(CheckLevel.ERROR, "warmup")
        for name, kind in schema.items():
            check = check.is_complete(name)
            if kind in ("float32", "float64", "int32", "int64"):
                check = check.is_non_negative(name)
            if kind in ("int32", "int64", "string"):
                check = check.is_unique(name)
        # the profiler's dataset warms the suite plan equally well
        VerificationSuite().on_data(ds).add_check(check).with_engine(
            engine
        ).run()
    return time.time() - t0


def default_engine_variants() -> list:
    """Engine-option variants that change the compiled program (each
    is a distinct plan-cache fingerprint; see engine/scan.py
    ``_plan_cache_key``)."""
    variants = [{}]
    # streaming wire, codecs on AND off: the codec-table token rides
    # the streaming plan fingerprint (engine/scan.py), so the codec-on
    # wire and the codecs-off differential oracle are two distinct
    # plans — warm both with the device cache off (the resident passes
    # above never build a wire). The probe-resolved codec table for
    # the synthetic data matches production only as far as the
    # synthetic value ranges do (wide_ints covers both int widths).
    variants.append({"device_cache_bytes": 0})
    variants.append({"device_cache_bytes": 0, "wire_codecs": False})
    # NO variants for the r10 ingest knobs (ingest_workers /
    # ingest_depth / ingest_lookahead / process_sharded_ingest): they
    # are host-pipeline concurrency settings read inside
    # _run_scan_streaming AFTER prepare_scan, so they are
    # plan-fingerprint-neutral by construction (the staticcheck
    # `plankey` gate enforces this) — every worker count reuses the
    # same warmed plan.
    return variants


def _mesh_engines(mesh_shapes):
    """(label, engine-or-None) per requested placement shape. ``None``
    in ``mesh_shapes`` warms the default (host/whole-backend) engine; an
    integer ``n`` warms an n-device ``Mesh`` — the SAME shape-keyed plan
    entry (engine/scan.py ``_placement_shape``) the elastic placer's
    n-device slices execute, whichever concrete devices the pool hands
    out. Shapes exceeding the host's device count are skipped (warming
    a shape the pool can never grant is dead work)."""
    engines = []
    for shape in mesh_shapes:
        if shape is None:
            engines.append(("default", None))
            continue
        import jax
        from jax.sharding import Mesh

        from deequ_tpu.engine.scan import AnalysisEngine

        devices = jax.devices()
        n = int(shape)
        if n < 1 or n > len(devices):
            continue
        mesh = Mesh(np.array(devices[:n]), ("dp",))
        engines.append((f"mesh{n}", AnalysisEngine(mesh=mesh)))
    return engines


def warm_plans(
    schema,
    suite: bool = False,
    batch_size=None,
    nullable=(False, True),
    wide_ints=None,
    high_card_strings=(False,),
    engine_variants=None,
    checks=None,
    profile: bool = True,
    mesh_shapes=(None,),
    log=None,
) -> dict:
    """Warm every fused-plan variant for ``schema`` and REPORT what got
    warmed — the reusable core behind both the CLI and the
    verification service's startup warmup (deequ_tpu/service).

    ``mesh_shapes`` extends the sweep across placement shapes: each
    entry is ``None`` (the default engine) or a device count ``n`` (an
    n-device mesh — the shape an elastic n-device slice executes).

    Returns ``{"tokens": [...], "already_warm": int, "passes": int,
    "total_s": float}`` where ``tokens`` are the structural plan-cache
    tokens (engine/scan.py ``plan_cache_snapshot``) ADDED by this call
    — the currency the service's PlanCache ledger tracks."""
    from deequ_tpu import config
    from deequ_tpu.engine.scan import DEFAULT_MAX_BATCH, plan_cache_snapshot

    batch = (
        batch_size or config.options().batch_size or DEFAULT_MAX_BATCH
    )
    # ONE batch of warm rows: compiles are shape-keyed, so more adds
    # nothing; engines resolve batch_size = min(rows, default), so the
    # warm row count must equal the production batch size exactly
    rows = batch
    has_int64 = any(k == "int64" for k in schema.values())
    has_string = any(k == "string" for k in schema.values())
    if wide_ints is None:
        wide_ints = (False, True) if has_int64 else (False,)
    if not has_string:
        high_card_strings = (False,)
    if engine_variants is None:
        engine_variants = default_engine_variants()

    engines = _mesh_engines(mesh_shapes)
    before = set(plan_cache_snapshot())
    total = 0.0
    passes = 0
    for variant in engine_variants:
        tag = (
            " ".join(f"{k}={v}" for k, v in variant.items()) or "default"
        )
        with config.configure(batch_size=batch, **variant):
            for shape_tag, engine in engines:
                for null in nullable:
                    for wide in wide_ints:
                        for high_card in high_card_strings:
                            t = warm_once(
                                schema, rows, null, wide, suite,
                                high_card_strings=high_card,
                                checks=checks, profile=profile,
                                engine=engine,
                            )
                            total += t
                            passes += 1
                            if log is not None:
                                log(
                                    f"  warmed [{tag}/{shape_tag}] "
                                    f"nullable={null} "
                                    f"wide_ints={wide} "
                                    f"high_card_strings={high_card}: "
                                    f"{t:.1f}s"
                                )
    after = plan_cache_snapshot()
    tokens = [t for t in after if t not in before]
    return {
        "tokens": tokens,
        "already_warm": len(before & set(after)),
        "passes": passes,
        "total_s": total,
    }


def main() -> int:
    parser = argparse.ArgumentParser(
        description="precompile deequ_tpu plans for a schema"
    )
    parser.add_argument("--schema", help="JSON {column: kind}")
    parser.add_argument(
        "--like-parquet", help="read the schema from a parquet file/dir"
    )
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument(
        "--nullable", choices=("none", "all", "both"), default="both"
    )
    parser.add_argument(
        "--int-width", choices=("narrow", "wide", "both"), default="both"
    )
    parser.add_argument(
        "--string-cardinality",
        choices=("low", "high", "both"),
        default="low",
        help="low: i8 codes + histogram pass; high: i32 codes, no "
        "histogram (two different compiled programs)",
    )
    parser.add_argument(
        "--suite", action="store_true",
        help="also warm a VerificationSuite-shaped plan",
    )
    parser.add_argument(
        "--mesh-shapes", default=None,
        help="comma-separated device counts to warm as mesh placement "
        "shapes (e.g. '1,2,4' for an elastic-placement service); "
        "'default' entries warm the host engine",
    )
    args = parser.parse_args()

    if bool(args.schema) == bool(args.like_parquet):
        parser.error("exactly one of --schema / --like-parquet")
    schema = (
        json.loads(args.schema)
        if args.schema
        else _schema_from_parquet(args.like_parquet)
    )
    if not schema:
        parser.error(
            "schema is empty (no supported columns) — nothing to warm"
        )
    for kind in schema.values():
        if kind not in _KINDS:
            parser.error(f"unknown kind {kind!r} (use one of {_KINDS})")
    print(f"schema: {schema}")

    from deequ_tpu import config

    nullables = {
        "none": (False,), "all": (True,), "both": (False, True)
    }[args.nullable]
    widths = {
        "narrow": (False,), "wide": (True,), "both": (False, True)
    }[args.int_width]
    cards = {
        "low": (False,), "high": (True,), "both": (False, True)
    }[args.string_cardinality]
    has_int64 = any(k == "int64" for k in schema.values())

    mesh_shapes = (None,)
    if args.mesh_shapes:
        mesh_shapes = tuple(
            None if part.strip() == "default" else int(part)
            for part in args.mesh_shapes.split(",")
            if part.strip()
        )

    report = warm_plans(
        schema,
        suite=args.suite,
        batch_size=args.batch_size,
        nullable=nullables,
        wide_ints=widths if has_int64 else (False,),
        high_card_strings=cards,
        mesh_shapes=mesh_shapes,
        log=print,
    )
    tokens = ", ".join(report["tokens"]) or "(all already resident)"
    print(f"warmed plan tokens: {tokens}")
    print(
        f"done in {report['total_s']:.1f}s ({report['passes']} passes) "
        f"— plans persisted to "
        f"{config.options().compilation_cache_dir}; the first "
        "production run now deserializes instead of compiling"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
