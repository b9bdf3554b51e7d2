"""Streaming row-level egress: one pass over a large table splits it
into a CLEAN parquet file and a QUARANTINE parquet file — every row
annotated with per-constraint outcomes and provenance — while the same
scan computes the aggregate verification metrics (docs/EGRESS.md).

The table is autosized for the current host with the bench's probe
(bench.py: ``probe_host``/``autosize``): the nominal shape is 100M rows
and small CI hosts scale down instead of thrashing. The pipeline is
honest about passes — for a mask/predicate suite the split streams out
of the SAME single traversal the metrics ride (``engine.data_passes``
rises by exactly 1).

Run: python examples/rowlevel_quarantine.py
"""

import os
import sys
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _sized, autosize, probe_host  # noqa: E402
from deequ_tpu import (  # noqa: E402
    Check,
    CheckLevel,
    Dataset,
    VerificationSuite,
    config,
)
from deequ_tpu.egress import RowLevelSink  # noqa: E402
from deequ_tpu.telemetry import get_telemetry  # noqa: E402

NOMINAL_ROWS = 100_000_000


def make_events(n: int) -> Dataset:
    """Synthetic event stream with realistic dirt: ~2% null emails,
    ~5% malformed addresses, ~1% negative amounts."""
    rng = np.random.default_rng(20260805)
    amount = rng.gamma(2.0, 40.0, n)
    amount[rng.random(n) < 0.01] *= -1.0
    user = rng.integers(0, max(1, n // 50), n)
    domain = np.where(rng.random(n) < 0.05, "bad address", "ex.com")
    email = np.char.add(
        np.char.add("u", user.astype("U12")), np.char.add("@", domain)
    ).astype(object)
    email[rng.random(n) < 0.02] = None
    return Dataset.from_arrow(
        pa.table(
            {
                "event_id": pa.array(np.arange(n, dtype=np.int64)),
                "amount": pa.array(amount),
                "email": pa.array(email, type=pa.string()),
            }
        )
    )


def main() -> None:
    sizing = autosize(probe_host())
    n = _sized(NOMINAL_ROWS, sizing, streamed=True)
    data = make_events(n)
    # the split is gigabytes at a host-sized run: removed when the
    # demo has checked it
    with tempfile.TemporaryDirectory(prefix="deequ_tpu_egress_") as d:
        split_and_check(data, n, d)


def split_and_check(data, n: int, out_dir: str) -> None:
    checks = [
        Check(CheckLevel.ERROR, "event hygiene")
        .is_complete("email")
        .has_pattern("email", r"@ex\.com$")
        .satisfies("amount >= 0", "amount_non_negative")
    ]
    sink = RowLevelSink(out_dir, tenant="examples", run_id="quarantine-demo")

    tm = get_telemetry()
    passes_before = tm.counter("engine.data_passes").value
    # device cache off: the source streams through once, host memory
    # stays O(batch), and the split is written as the scan folds
    with config.configure(device_cache_bytes=0):
        result = (
            VerificationSuite()
            .on_data(data)
            .add_checks(checks)
            .with_row_level_sink(sink)
            .run()
        )
    passes = tm.counter("engine.data_passes").value - passes_before

    report = result.row_level_egress
    print(f"rows           : {n:,}")
    print(f"status         : {report.status}")
    print(f"clean          : {report.rows_clean:,} -> {report.clean_dir}")
    print(
        f"quarantined    : {report.rows_quarantined:,} -> "
        f"{report.quarantine_dir}"
    )
    print(
        f"wire           : {report.bytes_raw:,} raw -> "
        f"{report.bytes_encoded:,} encoded bytes"
    )
    print(f"data passes    : {passes}")

    # the partitioning invariant: clean + quarantined == input,
    # and a mask/predicate suite needed exactly ONE traversal
    assert report.status == "complete"
    assert report.rows_clean + report.rows_quarantined == n
    assert passes == 1, passes
    clean = pq.read_table(report.clean_dir)
    quarantine = pq.read_table(report.quarantine_dir)
    assert len(clean) + len(quarantine) == n
    # every quarantined row names what it failed
    assert all(quarantine.column("__failed_constraints__").to_pylist())
    print("clean + quarantined == input; one pass — OK")


if __name__ == "__main__":
    main()
