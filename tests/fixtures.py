"""Canned data fixtures with known exact metric values (reference:
``src/test/scala/com/amazon/deequ/utils/FixtureSupport.scala``,
SURVEY.md §4)."""

import numpy as np
import pyarrow as pa

from deequ_tpu.data import Dataset


def df_full() -> Dataset:
    """4 complete rows."""
    return Dataset.from_pydict(
        {
            "item": ["1", "2", "3", "4"],
            "att1": ["a", "b", "a", "b"],
            "att2": ["c", "d", "d", "d"],
        }
    )


def df_missing() -> Dataset:
    """12 rows; att1 has 2 nulls (10/12 complete), att2 has 6 nulls
    (6/12 complete)."""
    att1 = ["a", "a", "b", "a", None, "a", "b", "a", "a", None, "b", "a"]
    att2 = ["f", "d", None, "f", None, "f", None, "d", None, None, None, "f"]
    return Dataset.from_pydict(
        {
            "item": [str(i + 1) for i in range(12)],
            "att1": att1,
            "att2": att2,
        }
    )


def df_numeric() -> Dataset:
    """6 rows of known numeric values: att1 = 1..6, att2 = 0,0,0,5,6,7."""
    return Dataset.from_pydict(
        {
            "item": ["1", "2", "3", "4", "5", "6"],
            "att1": [1, 2, 3, 4, 5, 6],
            "att2": [0, 0, 0, 5, 6, 7],
        }
    )


def df_numeric_with_nulls() -> Dataset:
    return Dataset.from_arrow(
        pa.table(
            {
                "att1": pa.array([1.0, None, 3.0, None, 5.0], pa.float64()),
                "att2": pa.array([2.0, 4.0, None, None, 10.0], pa.float64()),
            }
        )
    )


def df_unique() -> Dataset:
    """unique: all distinct; non_unique: a,a,b,b,c; half: a,a,b,c,d."""
    return Dataset.from_pydict(
        {
            "unique": ["1", "2", "3", "4", "5"],
            "non_unique": ["a", "a", "b", "b", "c"],
            "half": ["a", "a", "b", "c", "d"],
        }
    )


def df_strings() -> Dataset:
    return Dataset.from_pydict(
        {
            "email": [
                "someone@somewhere.org",
                "someone@else.com",
                "invalid-email",
                "other@domain.io",
            ],
            "name": ["foo", "bar", "foobar", None],
            "typed": ["1", "2.5", "true", "hello"],
        }
    )


def big_numeric(n: int = 100_000, seed: int = 7) -> Dataset:
    rng = np.random.default_rng(seed)
    return Dataset.from_pydict(
        {
            "x": rng.normal(10.0, 3.0, n),
            "y": rng.integers(0, 50, n),
        }
    )


def host_hll_registers(values, valid) -> np.ndarray:
    """int8[M] HLL registers of one column on the host: ``np.maximum.at``
    over the one (index, rank) derivation, the reference every engine
    path's register build must equal bit for bit."""
    import jax.numpy as jnp

    from deequ_tpu.sketches import hll

    h1, h2 = hll.hash_pair_numeric(jnp.asarray(values))
    idx, rho = hll._index_and_rank(h1, h2, jnp.asarray(valid))
    registers = np.zeros(hll.M, np.int32)
    np.maximum.at(registers, np.asarray(idx), np.asarray(rho))
    return registers.astype(hll.REGISTER_DTYPE)
