"""A TPC-DS ``store_sales``-faithful synthetic table, made from a seed.

Shared by ``bench.py`` and ``chip_smoke.py`` so both drive the same
data. Real ``store_sales`` measures are decimal(7,2) prices
(cent-quantized, ~10k distinct), small-int quantities (1..100) and
qty x price extended amounts, not continuous floats. Mix per 50
columns: 10 price-like f32 (every third with ~2% nulls), 5 quantity
i64, 5 extended-amount f64 (decimal columns read from parquet arrive as
float64; high cardinality), 10 continuous f32 normals, 10 i64 keys and
10 dictionary-encoded categorical strings.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

CATEGORIES = [f"cat_{j:03d}" for j in range(64)]
KEY_RANGE = 10_000_000


def store_sales_faithful(num_rows: int, num_cols: int, seed: int) -> pa.Table:
    rng = np.random.default_rng(seed)
    cols = {}
    n_price = num_cols // 5
    n_qty = num_cols // 10
    n_ext = num_cols // 10
    n_key = num_cols // 5
    n_cat = num_cols // 5
    n_cont = num_cols - n_price - n_qty - n_ext - n_key - n_cat
    for i in range(n_price):
        cents = rng.integers(50, 10_000, num_rows)  # $0.50 .. $99.99
        vals = cents.astype(np.float32) / 100
        mask = None
        if i % 3 == 0:
            mask = np.zeros(num_rows, bool)
            mask[rng.integers(0, num_rows, num_rows // 50)] = True
        cols[f"price{i}"] = pa.array(vals, pa.float32(), mask=mask)
    for i in range(n_qty):
        cols[f"qty{i}"] = pa.array(
            rng.integers(1, 101, num_rows, dtype=np.int64)
        )
    for i in range(n_ext):
        qty = rng.integers(1, 101, num_rows)
        cents = rng.integers(50, 10_000, num_rows)
        cols[f"ext{i}"] = pa.array((qty * cents) / 100, pa.float64())
    for i in range(n_cont):
        cols[f"m{i}"] = pa.array(
            rng.normal(100.0, 25.0, num_rows).astype(np.float32),
            pa.float32(),
        )
    for i in range(n_key):
        cols[f"k{i}"] = pa.array(
            rng.integers(0, KEY_RANGE, num_rows, dtype=np.int64)
        )
    dictionary = pa.array(CATEGORIES)
    for i in range(n_cat):
        codes = rng.integers(0, len(CATEGORIES), num_rows).astype(np.int32)
        cols[f"c{i}"] = pa.DictionaryArray.from_arrays(
            pa.array(codes), dictionary
        )
    return pa.table(cols)
