"""A 33-metric daily gate over TPC-DS ``customer`` (benchmark/
customer.py), run through ``VerificationSuite`` as one check: size;
the surrogate key complete and unique; the business key complete,
unique, 16 characters long, of 16 letters A..P, and its exact distinct
count; the e-mail address mostly complete, an e-mail, at most 50
characters, mostly unique, and its exact and approximate distinct
counts; approximate distinct counts and maximum lengths of the
names; isContainedIn on the salutation and the flag; the distinct
count of the birth country; the birth fields' ranges as compliance,
the birth year's extremes, mean, stddev and KLL median; the
completeness of the three current-dimension keys. The ids and e-mails
are near-unique, so they ride the program's hashed-string lane. Every
assertion holds on the reference, so a sound run reports SUCCESS."""

from __future__ import annotations

import math

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from benchmark.reference import HLL_REL_LIMIT, KLL_RANK_LIMIT, rank_err, rel_err

ID_PATTERN = r"^[A-P]{16}$"
SALUTATIONS = ("Mr.", "Mrs.", "Ms.", "Miss", "Sir", "Dr.")
FLAGS = ("Y", "N")
RANGES = (("c_birth_day", 1, 31), ("c_birth_month", 1, 12),
          ("c_birth_year", 1924, 1992))
CURRENT = ("c_current_cdemo_sk", "c_current_hdemo_sk", "c_current_addr_sk")
NAMES = (("c_first_name", 20), ("c_last_name", 30))
COLUMNS = ("c_customer_sk", "c_customer_id", "c_email_address",
           "c_first_name", "c_last_name", "c_salutation",
           "c_preferred_cust_flag", "c_birth_country") + tuple(
    c for c, _, _ in RANGES) + CURRENT

# the numbers compared and their limits, as verify34's (PERF.md §2)
NUMBERS = {
    "missing": 0,            # runs that raised or lacked a metric
    "exact_mismatch": 0,     # exact metrics (and statuses) that differ
    "wide_rel_err": 1e-9,    # mean, stddev (float64 accumulation)
    "hll_rel_err": HLL_REL_LIMIT,
    "kll_rank_err": KLL_RANK_LIMIT,
}


def _range_pred(col, lo, hi) -> str:
    return f"{col} IS NULL OR {col} BETWEEN {lo} AND {hi}"


def _check(rows: int):
    from deequ_tpu import Check, CheckLevel

    check = (
        Check(CheckLevel.ERROR, "customer gate")
        .has_size(lambda n: n == rows)
        .is_complete("c_customer_sk")
        .is_unique("c_customer_sk")
        .is_complete("c_customer_id")
        .is_unique("c_customer_id")
        .has_min_length("c_customer_id", lambda n: n == 16)
        .has_max_length("c_customer_id", lambda n: n == 16)
        .has_pattern("c_customer_id", ID_PATTERN)
        .has_completeness("c_email_address", lambda c: c > 0.9)
        .contains_email("c_email_address", lambda f: f > 0.9)
        .has_approx_count_distinct("c_email_address", lambda d: d > 0)
        .has_max_length("c_email_address", lambda n: n <= 50)
        .has_uniqueness("c_email_address", lambda u: u > 0.5)
        .has_number_of_distinct_values("c_email_address", lambda d: d > 0)
        .has_number_of_distinct_values("c_customer_id",
                                       lambda d: d == rows)
    )
    for col, width in NAMES:
        check = (check.has_approx_count_distinct(col, lambda d: d > 0)
                 .has_max_length(col, lambda n, w=width: n <= w))
    check = (
        check.is_contained_in("c_salutation", list(SALUTATIONS))
        .is_contained_in("c_preferred_cust_flag", list(FLAGS))
        .has_number_of_distinct_values("c_birth_country",
                                       lambda d: 0 < d <= 211)
    )
    for col, lo, hi in RANGES:
        check = check.satisfies(_range_pred(col, lo, hi), f"{col} range",
                                lambda f: f == 1.0)
    check = (
        check.has_min("c_birth_year", lambda v: v >= 1924)
        .has_max("c_birth_year", lambda v: v <= 1992)
        .has_mean("c_birth_year", lambda v: 1924 < v < 1992)
        .has_standard_deviation("c_birth_year", lambda v: v > 0)
        .has_approx_quantile("c_birth_year", 0.5, lambda v: v > 0)
    )
    for col in CURRENT:
        check = check.has_completeness(col, lambda c: c > 0.9)
    return check


def _metrics():
    """(analyzer, kind, column, argument) for every metric the check
    computes."""
    from deequ_tpu.analyzers import (
        ApproxCountDistinct, ApproxQuantile, Completeness, Compliance,
        CountDistinct, Maximum, MaxLength, Mean, Minimum, MinLength,
        PatternMatch, Size, StandardDeviation, Uniqueness,
    )
    from deequ_tpu.checks.check import PATTERN_EMAIL

    out = [(Size(), "size", None, None)]
    for col in ("c_customer_sk", "c_customer_id"):
        out += [(Completeness(col), "completeness", col, None),
                (Uniqueness(col), "uniqueness", col, None)]
    out += [
        (MinLength("c_customer_id"), "min_length", "c_customer_id", None),
        (MaxLength("c_customer_id"), "max_length", "c_customer_id", None),
        (PatternMatch("c_customer_id", ID_PATTERN), "pattern",
         "c_customer_id", ID_PATTERN),
        (Completeness("c_email_address"), "completeness",
         "c_email_address", None),
        (PatternMatch("c_email_address", PATTERN_EMAIL), "pattern",
         "c_email_address", PATTERN_EMAIL),
        (ApproxCountDistinct("c_email_address"), "hll", "c_email_address",
         None),
        (MaxLength("c_email_address"), "max_length", "c_email_address",
         None),
        (Uniqueness("c_email_address"), "uniqueness", "c_email_address",
         None),
        (CountDistinct("c_email_address"), "distinct", "c_email_address",
         None),
        (CountDistinct("c_customer_id"), "distinct", "c_customer_id", None),
    ]
    for col, _ in NAMES:
        out += [(ApproxCountDistinct(col), "hll", col, None),
                (MaxLength(col), "max_length", col, None)]
    for col, allowed in (("c_salutation", SALUTATIONS),
                         ("c_preferred_cust_flag", FLAGS)):
        # as Check.is_contained_in builds it
        quoted = ", ".join(f"'{v}'" for v in allowed)
        out.append((Compliance(f"{col} contained in {','.join(allowed)}",
                               f"{col} IS NULL OR {col} IN ({quoted})"),
                    "contained", col, allowed))
    out.append((CountDistinct("c_birth_country"), "distinct",
                "c_birth_country", None))
    for col, lo, hi in RANGES:
        out.append((Compliance(f"{col} range", _range_pred(col, lo, hi)),
                    "in_range", col, (lo, hi)))
    out += [
        (Minimum("c_birth_year"), "minimum", "c_birth_year", None),
        (Maximum("c_birth_year"), "maximum", "c_birth_year", None),
        (Mean("c_birth_year"), "mean", "c_birth_year", None),
        (StandardDeviation("c_birth_year"), "stddev", "c_birth_year", None),
        (ApproxQuantile("c_birth_year", 0.5), "kll", "c_birth_year", 0.5),
    ]
    for col in CURRENT:
        out.append((Completeness(col), "completeness", col, None))
    return out


class Prepared:
    def __init__(self, rows: int):
        self.rows = rows
        self.check = _check(rows)
        self.metrics = _metrics()


def prepare(rows: int) -> Prepared:
    return Prepared(rows)


def run(prepared: Prepared, ds, engine=None) -> dict:
    """The timed call: one suite run, and its answers as plain values
    (a metric that failed is left out, and so counts as missing)."""
    from deequ_tpu import VerificationSuite

    builder = VerificationSuite().on_data(ds).add_check(prepared.check)
    if engine is not None:
        builder = builder.with_engine(engine)
    result = builder.run()
    values = {}
    for analyzer, _, _, _ in prepared.metrics:
        metric = result.metrics.get(analyzer)
        if metric is not None and metric.value.is_success:
            values[str(analyzer)] = float(metric.value.get())
    return {"status": result.status.name, "values": values}


def reference_jobs(prepared: Prepared, table=None):
    jobs = [("sorted", "c_birth_year"), ("valid", "c_birth_year")]
    jobs += [("distinct", c) for c in (
        "c_customer_id", "c_email_address", "c_first_name", "c_last_name",
        "c_birth_country")]
    return jobs


def _uniqueness(ref, col) -> float:
    """Values seen once over the rows where the column is not null,
    as grouping metrics count them."""
    def compute():
        counts = pc.value_counts(ref._col(col).drop_null()).field(
            "counts").to_numpy()
        return int(np.count_nonzero(counts == 1)) / int(counts.sum())

    return ref._memoized(("unique_of_non_null", col), compute)


def _share(ref, mask) -> float:
    """Rows where ``mask`` holds over all rows; null counts as false."""
    return ref.compliance(np.asarray(
        pc.fill_null(mask, False).to_numpy(zero_copy_only=False)))


def _want(ref, kind: str, col, arg):
    if kind == "size":
        return float(ref.n)
    if kind in ("distinct", "hll"):
        return float(ref.distinct(col))
    if kind == "uniqueness":
        return _uniqueness(ref, col)
    if kind in ("min_length", "max_length"):
        lengths = pc.utf8_length(ref._col(col))
        return float((pc.min if kind == "min_length" else pc.max)(
            lengths).as_py())
    if kind == "pattern":
        return _share(ref, pc.match_substring_regex(ref._col(col), arg))
    if kind == "contained":
        values = ref._col(col)
        return _share(ref, pc.or_kleene(pc.is_null(values), pc.is_in(
            values, pa.array(list(arg), values.type))))
    if kind == "in_range":
        values = ref.table.column(col)
        lo, hi = arg
        return _share(ref, pc.or_kleene(pc.is_null(values), pc.and_kleene(
            pc.greater_equal(values, lo), pc.less_equal(values, hi))))
    return getattr(ref, kind)(col)


def compare(prepared: Prepared, ref, answers, readings, label="") -> None:
    """Fold every answer's distance from the reference into
    ``readings``."""
    wants = {}
    for analyzer, kind, col, arg in prepared.metrics:
        if kind != "kll":
            wants[str(analyzer)] = _want(ref, kind, col, arg)
    for i, answer in enumerate(answers):
        where = f"{label}run {i}"
        if answer is None:
            readings.count("missing", f"{where}: raised")
            continue
        if answer["status"] != "SUCCESS":
            readings.count("exact_mismatch",
                           f"{where}: status {answer['status']}")
        for analyzer, kind, col, q in prepared.metrics:
            key = str(analyzer)
            got = answer["values"].get(key)
            name = f"{where} {key}"
            if got is None:
                readings.count("missing", f"{name}: no metric")
            elif kind == "kll":
                readings.add("kll_rank_err",
                             rank_err(q, ref.rank_interval(col, got)), name)
            elif kind == "hll":
                readings.add("hll_rel_err", rel_err(got, wants[key]), name)
            elif kind in ("mean", "stddev"):
                readings.add("wide_rel_err", rel_err(got, wants[key]), name)
            elif got != wants[key] or not math.isfinite(got):
                readings.count("exact_mismatch",
                               f"{name}: {got!r} != {wants[key]!r}")
