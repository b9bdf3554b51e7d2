"""The string-heavy yardstick: TPC-DS ``customer`` made from a seed.

The table is the spec's own 18 columns (TPC-DS v3.2.0, clause 2,
``customer``) at the widths the spec gives them: the int64 surrogate
key and its 16-letter business key, five int64 foreign keys into the
configuration's dimensions, three int32 birth fields and eight strings
(salutation, first and last name, the preferred-customer flag, the
birth country, the login and the e-mail address). Every number that
sizes it (rows, the dimensions' row counts, the ranges, the vocabulary
sizes, the null rate) comes from the configuration's ``domains``.

- ``c_customer_id`` is dsdgen's business key of the surrogate key:
  the high and then the low 32 bits, each as eight letters 'A'..'P',
  one per 4-bit digit, low digit first (key 1 is AAAAAAAABAAAAAAA);
  so it is unique, like the key.
- ``c_email_address`` is ``<first>.<last>@<domain>.<tld>`` over the
  row's own names, a random lower-case domain of 5..13 letters and a
  top-level domain: near-unique.
- Names and countries are pseudo-words drawn once from the seed into
  vocabularies of the configured sizes (dsdgen's word lists are not at
  hand); salutations and the flag are the spec's values.

Strings are built in vectorized form (numpy byte matrices turned into
Arrow buffers, Arrow's element-wise joins), never value by value. Every
draw has its own child seed (``SeedSequence.spawn``) and the draws run
in parallel threads, so the same seed gives the same table whatever the
thread count.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

FOREIGN_KEYS = {"c_current_cdemo_sk": "cdemo", "c_current_hdemo_sk": "hdemo",
                "c_current_addr_sk": "addr"}
DATES = ("c_first_shipto_date_sk", "c_first_sales_date_sk",
         "c_last_review_date_sk")
BIRTH = {"c_birth_day": "birth_day", "c_birth_month": "birth_month",
         "c_birth_year": "birth_year"}
# the spec's column order
COLUMNS = ("c_customer_sk", "c_customer_id") + tuple(FOREIGN_KEYS) + DATES[
    :2] + ("c_salutation", "c_first_name", "c_last_name",
           "c_preferred_cust_flag") + tuple(BIRTH) + (
    "c_birth_country", "c_login", "c_email_address", DATES[2])
STRINGS = ("c_customer_id", "c_salutation", "c_first_name", "c_last_name",
           "c_preferred_cust_flag", "c_birth_country", "c_login",
           "c_email_address")
# never null: the key and its business key; c_login is null throughout
NOT_NULL = ("c_customer_sk", "c_customer_id")
DRAWS = ("words", "first", "last", "salutation", "flag", "country",
         "domain", "domain_len", "tld") + tuple(FOREIGN_KEYS) + DATES + (
    tuple(BIRTH)) + tuple(f"null:{c}" for c in COLUMNS if c not in NOT_NULL)


def _strings(chars: np.ndarray, lengths: np.ndarray) -> pa.Array:
    """(n, w) byte matrix, each row's first ``lengths`` bytes -> utf8."""
    n, w = chars.shape
    keep = np.arange(w)[None, :] < lengths[:, None]
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    return pa.LargeStringArray.from_buffers(
        n, pa.py_buffer(offsets), pa.py_buffer(
            np.ascontiguousarray(chars[keep], dtype=np.uint8)))


def _words(count: int, lo: int, hi: int, rng) -> pa.Array:
    """``count`` distinct capitalized pseudo-words of lo..hi letters."""
    chars = rng.integers(97, 123, (4 * count, hi), dtype=np.uint8)
    chars[:, 0] -= 32
    words = pc.unique(_strings(chars, rng.integers(lo, hi + 1, 4 * count)))
    return words.slice(0, count)


def business_key(sk: np.ndarray) -> pa.Array:
    """dsdgen's 16-letter business key of each surrogate key."""
    nibbles = np.arange(8, dtype=np.uint64) * np.uint64(4)
    u = sk.astype(np.uint64)
    hi = (u >> np.uint64(32))[:, None] >> nibbles & np.uint64(15)
    lo = (u & np.uint64(0xFFFFFFFF))[:, None] >> nibbles & np.uint64(15)
    chars = (np.concatenate([hi, lo], axis=1) + np.uint64(65)).astype(
        np.uint8)
    return _strings(chars, np.full(len(sk), 16))


def _with_nulls(arr: pa.Array, null) -> pa.Array:
    if null is None or not null.any():
        return arr
    return pc.if_else(pa.array(null), pa.scalar(None, arr.type), arr)


def customer(config: dict, seed, threads: int = 12) -> pa.Table:
    """The configuration's ``customer`` table of ``seed`` (any
    non-negative int, or a ``SeedSequence``)."""
    rows = int(config["rows"])
    d = config["domains"]
    root = (seed if isinstance(seed, np.random.SeedSequence)
            else np.random.SeedSequence(int(seed)))
    rngs = {name: np.random.default_rng(s)
            for name, s in zip(DRAWS, root.spawn(len(DRAWS)))}
    sk = np.arange(1, rows + 1, dtype=np.int64)

    def vocabulary():
        rng = rngs["words"]
        return {
            "first": _words(d["first_names"], *d["first_name_len"], rng),
            "last": _words(d["last_names"], *d["last_name_len"], rng),
            "country": pc.utf8_upper(
                _words(d["countries"], *d["country_len"], rng)),
        }

    def uniform(name, lo, hi, dtype=np.int64):
        return rngs[name].integers(lo, hi + 1, rows).astype(dtype)

    jobs = {
        "vocabulary": vocabulary,
        "c_customer_id": lambda: business_key(sk),
        "first": lambda: uniform("first", 0, d["first_names"] - 1),
        "last": lambda: uniform("last", 0, d["last_names"] - 1),
        "country": lambda: uniform("country", 0, d["countries"] - 1),
        "salutation": lambda: uniform(
            "salutation", 0, len(d["salutations"]) - 1),
        "flag": lambda: uniform("flag", 0, 1),
        "domain": lambda: _strings(
            rngs["domain"].integers(97, 123, (rows, d["domain_len"][1]),
                                    dtype=np.uint8),
            uniform("domain_len", *d["domain_len"])),
        "tld": lambda: uniform("tld", 0, len(d["tlds"]) - 1),
    }
    for c, dim in FOREIGN_KEYS.items():
        jobs[c] = (lambda c=c, dim=dim: uniform(c, 1, d[dim]))
    for c in DATES:
        jobs[c] = (lambda c=c: uniform(c, *d[c[2:]]))
    for c, dom in BIRTH.items():
        jobs[c] = (lambda c=c, dom=dom: uniform(c, *d[dom], np.int32))
    for c in COLUMNS:
        if c not in NOT_NULL:
            jobs[f"null:{c}"] = (lambda c=c: rngs[f"null:{c}"].random(
                rows, np.float32) < d["null_rate"])
    with ThreadPoolExecutor(max_workers=threads) as pool:
        futures = {k: pool.submit(f) for k, f in jobs.items()}
        v = {k: f.result() for k, f in futures.items()}

    words = v["vocabulary"]
    first = words["first"].take(pa.array(v["first"]))
    last = words["last"].take(pa.array(v["last"]))

    def sep(text):
        return pa.scalar(text, pa.large_string())

    host = pc.binary_join_element_wise(
        v["domain"], pa.array(d["tlds"], pa.large_string()).take(
            pa.array(v["tld"])), sep("."))
    email = pc.binary_join_element_wise(
        pc.binary_join_element_wise(first, last, sep(".")), host, sep("@"))
    values = {
        "c_customer_sk": pa.array(sk),
        "c_customer_id": v["c_customer_id"],
        "c_salutation": pa.array(d["salutations"]).take(
            pa.array(v["salutation"])),
        "c_first_name": first,
        "c_last_name": last,
        "c_preferred_cust_flag": pa.array(["N", "Y"]).take(
            pa.array(v["flag"])),
        "c_birth_country": words["country"].take(pa.array(v["country"])),
        "c_login": pa.nulls(rows, pa.string()),
        "c_email_address": email,
    }
    for c in tuple(FOREIGN_KEYS) + DATES + tuple(BIRTH):
        values[c] = pa.array(v[c])

    def column(name):
        arr = values[name]
        if pa.types.is_large_string(arr.type):
            arr = arr.cast(pa.string())
        return _with_nulls(arr, v.get(f"null:{name}"))

    with ThreadPoolExecutor(max_workers=threads) as pool:
        arrays = list(pool.map(column, COLUMNS))
    return pa.table(dict(zip(COLUMNS, arrays)))


def dictionary_encoded(table: pa.Table) -> pa.Table:
    """The table with its string columns as Arrow dictionary arrays
    (what the harness's byte count reads: an index width per value)."""
    out = table
    for i, name in enumerate(table.column_names):
        if pa.types.is_string(table.schema.field(name).type):
            out = out.set_column(i, name,
                                 pc.dictionary_encode(table.column(name)))
    return out
