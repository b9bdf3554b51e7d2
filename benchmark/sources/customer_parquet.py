"""TPC-DS ``customer`` (benchmark/customer.py) written in set-up as
``shards`` parquet files under the checkout and streamed back: every
run opens a new ``Dataset.from_parquet``, so every run reads the files
(from the page cache) and decides its columns' representations anew.

The cell exists to measure the program's hashed-string lane: a program
without one (``Dataset.string_lane``) would build a 12M-entry host
dictionary per run and then overflow its dictionary-delta capacity, so
the source refuses it before any data is made."""

from __future__ import annotations

import os
import shutil
from concurrent.futures import ThreadPoolExecutor

from benchmark import customer
from benchmark.traffic import seed_sequence


class Source:
    def __init__(self, config, traffic, seed, workdir):
        import pyarrow.parquet as pq

        from deequ_tpu import Dataset

        if not hasattr(Dataset, "string_lane"):
            raise SystemExit(
                "customer_parquet: this program has no hashed-string lane "
                "(Dataset.string_lane); the cell cannot run on it")
        self.table = customer.customer(config, seed_sequence(seed))
        self._tables = None
        self.dir = os.path.join(workdir, "parquet")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        shards = int(traffic["source"]["shards"])
        step = -(-self.table.num_rows // shards)

        def write(k):
            pq.write_table(self.table.slice(k * step, step),
                           os.path.join(self.dir, f"part-{k:03d}.parquet"))

        with ThreadPoolExecutor(max_workers=shards) as pool:
            list(pool.map(write, range(shards)))

    def dataset(self, i: int):
        from deequ_tpu import Dataset

        return Dataset.from_parquet(self.dir)

    def rows(self, i: int) -> int:
        return self.table.num_rows

    def tables(self):
        """The host table the runs read, its strings as dictionary
        arrays (made once, after the window)."""
        if self._tables is None:
            self._tables = [customer.dictionary_encoded(self.table)]
        return self._tables

    def part_of(self, i: int) -> int:
        return 0

    def release(self) -> None:
        pass

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
