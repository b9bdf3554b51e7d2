"""Deterministic test doubles for the resilience machinery
(docs/RESILIENCE.md) and seeded synthetic tables (``tpcds``, shared by
bench.py and chip_smoke.py). Not imported by library code."""

from deequ_tpu.testing.faults import FaultInjectingDataset

__all__ = ["FaultInjectingDataset"]
