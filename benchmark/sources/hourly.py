"""The configuration's ``store_sales`` generator cut into ``partitions``
consecutive partitions of ``partition_rows`` rows, an hour of sales
each: run ``i`` verifies partition ``i`` mod ``partitions`` through a
new ``Dataset.from_arrow``, so with the device cache off every
partition is uploaded anew, as an hourly gate uploads each new
partition once."""

from __future__ import annotations

from benchmark import tables
from benchmark.traffic import seed_sequence


class Source:
    def __init__(self, config, traffic, seed, workdir):
        src = traffic["source"]
        self.n = int(src["partitions"])
        self.width = int(src["partition_rows"])
        whole = tables.store_sales(dict(config, rows=self.n * self.width),
                                   seed_sequence(seed))
        self.parts = [whole.slice(k * self.width, self.width)
                      for k in range(self.n)]

    def dataset(self, i: int):
        from deequ_tpu import Dataset

        return Dataset.from_arrow(self.parts[self.part_of(i)])

    def rows(self, i: int) -> int:
        return self.width

    def tables(self):
        """Each partition's host table, by partition."""
        return self.parts

    def part_of(self, i: int) -> int:
        return i % self.n

    def release(self) -> None:
        pass

    def close(self) -> None:
        pass
