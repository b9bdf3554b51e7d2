"""The hashed-string lane (data/strings.py): string columns past
``dict_delta_capacity`` distinct values ship per-row hashes and planes
instead of dictionary codes, on every path, and stay exact.

A customer-shaped table (TPC-DS ``customer``'s string columns) of
150,000 rows, whose ids and emails are distinct far past 65,536, runs
resident, streamed from two parquet shards and on the 8-device CPU
mesh; each path must agree with a plain Arrow/numpy reference and give
equal HLL registers. The vectorized host helpers are pinned against
per-value Python loops."""

import math
import re

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from deequ_tpu import config
from deequ_tpu.analyzers import (
    ApproxCountDistinct,
    CountDistinct,
    Completeness,
    Compliance,
    DataType,
    Distinctness,
    Entropy,
    MaxLength,
    MinLength,
    PatternMatch,
    Size,
    Uniqueness,
)
from deequ_tpu.analyzers.datatype import classify_string
from deequ_tpu.analyzers.runner import AnalysisRunner
from deequ_tpu.analyzers.states import STATE_FORMAT_VERSIONS
from deequ_tpu.checks.check import PATTERN_EMAIL, PATTERN_SSN
from deequ_tpu.data import Dataset
from deequ_tpu.data import strings
from deequ_tpu.engine import AnalysisEngine
from deequ_tpu.io.state_provider import (
    FileSystemStateProvider,
    InMemoryStateProvider,
)
from deequ_tpu.telemetry import get_telemetry

ROWS = 150_000
ID_PATTERN = r"^[A-P]{16}$"
SALUTATIONS = ("Mr.", "Mrs.", "Ms.", "Miss", "Sir", "Dr.")
HLL_LIMIT = 4 * 1.04 / math.sqrt(1 << 14)


def _bkey(sk: np.ndarray) -> list:
    """dsdgen's 16-letter A-P business key of a surrogate key."""
    out = []
    for k in sk.tolist():
        hi, lo = k >> 32, k & 0xFFFFFFFF
        out.append("".join(chr(65 + ((hi >> (4 * i)) & 15)) for i in range(8))
                   + "".join(chr(65 + ((lo >> (4 * i)) & 15)) for i in range(8)))
    return out


def customer_table(rows: int = ROWS, seed: int = 7) -> pa.Table:
    rng = np.random.default_rng(seed)
    first = [f"F{j}n" for j in range(400)]
    last = [f"L{j}son" for j in range(600)]
    domains = [f"d{j}" for j in range(300)]
    f, l, d = (rng.integers(0, len(p), rows) for p in (first, last, domains))
    null = rng.random((4, rows)) < 0.03
    emails = [None if null[0, i] else
              f"{first[f[i]]}.{last[l[i]]}@{domains[d[i]]}.com"
              for i in range(rows)]
    # numbers, booleans and words: a lane column for DataType
    kinds = rng.integers(0, 5, rows)
    nums = rng.integers(0, 10**7, rows)
    codes = [None if null[1, i] else
             (str(nums[i]), f"{nums[i]}.5", "true" if nums[i] % 2 else
              "FALSE", f"w{nums[i]}", f"+{nums[i]}e3")[kinds[i]]
             for i in range(rows)]
    salutation = [None if null[2, i] else SALUTATIONS[s]
                  for i, s in enumerate(rng.integers(0, 6, rows))]
    return pa.table({
        "c_customer_sk": pa.array(np.arange(1, rows + 1)),
        "c_customer_id": pa.array(_bkey(np.arange(1, rows + 1))),
        "c_email_address": pa.array(emails),
        "c_code": pa.array(codes),
        "c_salutation": pa.array(salutation),
        "c_first_name": pa.array([first[i] for i in f]),
    })


@pytest.fixture(scope="module")
def table():
    return customer_table()


@pytest.fixture(scope="module")
def shards(table, tmp_path_factory):
    directory = tmp_path_factory.mktemp("customer")
    half = table.num_rows // 2
    pq.write_table(table.slice(0, half), str(directory / "a.parquet"))
    pq.write_table(table.slice(half), str(directory / "b.parquet"))
    return str(directory)


ANALYZERS = [
    Size(),
    Uniqueness(["c_customer_id"]),
    CountDistinct(["c_customer_id"]),
    Uniqueness(["c_email_address"]),
    CountDistinct(["c_email_address"]),
    Distinctness(["c_email_address"]),
    Entropy(["c_email_address"]),
    PatternMatch("c_customer_id", ID_PATTERN),
    PatternMatch("c_email_address", PATTERN_EMAIL),
    MinLength("c_customer_id"),
    MaxLength("c_email_address"),
    Compliance("salutation", "c_salutation IS NULL OR c_salutation "
               "IN ('Mr.', 'Mrs.', 'Dr.')"),
    Compliance("email in", "c_email_address IN ('F1n.L1son@d1.com', "
               "'F2n.L2son@d2.com') OR c_email_address = 'F3n.L3son@d3.com'"),
    Compliance("email like", "c_email_address LIKE 'F1%'"),
    Compliance("email null", "c_email_address IS NULL"),
    DataType("c_code"),
    ApproxCountDistinct("c_customer_id"),
    ApproxCountDistinct("c_email_address"),
    ApproxCountDistinct("c_first_name"),
    Completeness("c_email_address"),
]


def reference(table: pa.Table) -> dict:
    """Plain Arrow/numpy answers, keyed like the runs' metrics."""
    n = table.num_rows
    out = {str(Size()): float(n)}
    # grouping metrics count the rows whose column is not null
    for c in ("c_customer_id", "c_email_address"):
        col = table.column(c)
        counts = pc.value_counts(col.drop_null()).field("counts").to_numpy()
        out[str(Uniqueness([c]))] = (
            np.count_nonzero(counts == 1) / counts.sum())
        out[str(CountDistinct([c]))] = float(len(counts))
    email = table.column("c_email_address")
    counts = pc.value_counts(email.drop_null()).field("counts").to_numpy()
    p = counts / counts.sum()
    out[str(Distinctness(["c_email_address"]))] = len(counts) / counts.sum()
    out[str(Entropy(["c_email_address"]))] = float(-(p * np.log(p)).sum())

    def share(mask):
        return np.count_nonzero(
            pc.fill_null(mask, False).to_numpy(zero_copy_only=False)) / n

    out[str(PatternMatch("c_customer_id", ID_PATTERN))] = share(
        pc.match_substring_regex(table.column("c_customer_id"), ID_PATTERN))
    out[str(PatternMatch("c_email_address", PATTERN_EMAIL))] = share(
        pc.match_substring_regex(email, PATTERN_EMAIL))
    out[str(MinLength("c_customer_id"))] = float(
        pc.min(pc.utf8_length(table.column("c_customer_id"))).as_py())
    out[str(MaxLength("c_email_address"))] = float(
        pc.max(pc.utf8_length(email)).as_py())
    sal = table.column("c_salutation")
    out[str(ANALYZERS[11])] = share(pc.or_(
        pc.is_null(sal), pc.is_in(sal, pa.array(["Mr.", "Mrs.", "Dr."]))))
    out[str(ANALYZERS[12])] = share(pc.is_in(email, pa.array(
        ["F1n.L1son@d1.com", "F2n.L2son@d2.com", "F3n.L3son@d3.com"])))
    out[str(ANALYZERS[13])] = share(pc.starts_with(email, "F1"))
    out[str(ANALYZERS[14])] = share(pc.is_null(email))
    out[str(Completeness("c_email_address"))] = 1 - email.null_count / n
    buckets = np.array([
        0 if v is None else classify_string(v)
        for v in table.column("c_code").to_pylist()])
    out[str(DataType("c_code"))] = np.bincount(buckets, minlength=5)[:5]
    for c in ("c_customer_id", "c_email_address", "c_first_name"):
        out[str(ApproxCountDistinct(c))] = float(
            pc.count_distinct(table.column(c)).as_py())
    return out


def _run(dataset, engine=None, states=None, **options):
    with config.configure(**options):
        ctx = AnalysisRunner.do_analysis_run(
            dataset, ANALYZERS, engine=engine, save_states_with=states)
    return ctx, states


def _paths(name, table, shards, cpu_mesh, states=None):
    if name == "resident":
        return _run(Dataset.from_arrow(table), states=states)
    if name == "streamed":
        return _run(Dataset.from_parquet(shards), states=states,
                    device_cache_bytes=0, batch_size=16_384)
    return _run(Dataset.from_arrow(table), states=states,
                engine=AnalysisEngine(mesh=cpu_mesh, batch_size=8_192))


def _check_against_reference(ctx, want):
    for a in ANALYZERS:
        metric = ctx.metric(a)
        assert metric.value.is_success, (a, metric.value)
        got = metric.value.get()
        if isinstance(a, DataType):
            got = [got.values[k].absolute for k in (
                "Unknown", "Fractional", "Integral", "Boolean", "String")]
            assert got == list(want[str(a)]), a
        elif isinstance(a, ApproxCountDistinct):
            assert abs(got - want[str(a)]) / want[str(a)] <= HLL_LIMIT, a
        elif isinstance(a, Entropy):
            assert got == pytest.approx(want[str(a)], rel=1e-12), a
        else:
            assert got == want[str(a)], a


@pytest.fixture(scope="module")
def want(table):
    return reference(table)


class TestPaths:
    @pytest.mark.parametrize("path", ["resident", "streamed", "mesh"])
    def test_agrees_with_reference(self, path, table, shards, want,
                                   cpu_mesh):
        lanes = get_telemetry().counter("strings.lane_columns")
        before = lanes.value
        ctx, _ = _paths(path, table, shards, cpu_mesh)
        _check_against_reference(ctx, want)
        # ids, emails and codes are lanes; names and salutations keep
        # their dictionaries
        assert lanes.value - before == 3

    def test_registers_equal_on_every_path(self, table, shards, cpu_mesh):
        registers = []
        for path in ("resident", "streamed", "mesh"):
            _, states = _paths(path, table, shards, cpu_mesh,
                               InMemoryStateProvider())
            registers.append([
                np.asarray(states.load(ApproxCountDistinct(c)).registers)
                for c in ("c_customer_id", "c_email_address")
            ])
        for other in registers[1:]:
            for a, b in zip(registers[0], other):
                np.testing.assert_array_equal(a, b)

    def test_lane_and_dictionary_registers_are_equal(self, table):
        """A string lands in the same register whether its column ships
        row hashes or dictionary codes, so the states merge."""
        got = []
        for capacity in (1 << 16, 1 << 20):  # lane, then dictionary
            states = InMemoryStateProvider()
            with config.configure(dict_delta_capacity=capacity):
                ds = Dataset.from_arrow(table)
                AnalysisRunner.do_analysis_run(
                    ds, [ApproxCountDistinct("c_email_address")],
                    save_states_with=states)
            assert ds.string_lane("c_email_address") == (capacity == 1 << 16)
            got.append(np.asarray(states.load(
                ApproxCountDistinct("c_email_address")).registers))
        np.testing.assert_array_equal(got[0], got[1])


class TestExactness:
    @pytest.mark.parametrize("path", ["resident", "streamed"])
    def test_forced_collisions_stay_exact(self, path, table, shards, want,
                                          monkeypatch):
        """With a hash of 97 values every lane group is a pile of
        unequal strings; the host re-read splits them exactly."""
        real = strings.hash64
        monkeypatch.setattr(strings, "hash64",
                            lambda arr: real(arr) % np.uint64(97))
        records = []
        get_telemetry().add_span_sink(records.append)
        try:
            ctx, _ = _paths(path, table, shards, None)
        finally:
            get_telemetry().remove_span_sink(records.append)
        for a in ANALYZERS[1:7]:
            got = ctx.metric(a).value.get()
            assert got == pytest.approx(want[str(a)], rel=1e-12), a
        found = {r["attributes"]["column"]: r["attributes"]["collisions"]
                 for r in records if r["name"] == "spill:collisions"}
        distinct = want[str(CountDistinct(["c_email_address"]))]
        assert found["c_email_address"] == distinct - 97
        assert found["c_customer_id"] == ROWS - 97

    def test_no_repeated_hash_costs_no_re_read(self, shards):
        """Unique ids: no hash repeats, no collision traversal; a lane
        column is never a dictionary pre-pass."""
        records = []
        passes = get_telemetry().counter("engine.data_passes")
        get_telemetry().add_span_sink(records.append)
        try:
            with config.configure(device_cache_bytes=0, batch_size=16_384):
                before = passes.value
                ctx = AnalysisRunner.do_analysis_run(
                    Dataset.from_parquet(shards),
                    [Uniqueness(["c_customer_id"]),
                     ApproxCountDistinct("c_email_address"),
                     PatternMatch("c_customer_id", ID_PATTERN)])
                assert passes.value - before == 1
        finally:
            get_telemetry().remove_span_sink(records.append)
        assert ctx.metric(Uniqueness(["c_customer_id"])).value.get() == 1.0
        names = [r["name"] for r in records]
        assert "traverse:uniques" not in names
        assert "traverse:collisions" not in names
        (resolve,) = [r for r in records if r["name"] == "spill:collisions"]
        assert resolve["attributes"]["hashes"] == 0
        lane = [r for r in records if r["name"] == "ingest:strings"]
        assert sum(r["attributes"]["rows"] for r in lane) == 3 * ROWS


class TestHostHelpers:
    M = (1 << 64) - 1

    def _py_hash64(self, b: bytes) -> int:
        """The string hash, one value at a time in Python ints."""
        m = self.M
        h = ((len(b) * 0x9E3779B185EBCA87) & m) ^ 0x165667B19E3779F9
        for k in range(0, len(b), 8):
            w = int.from_bytes(b[k:k + 8].ljust(8, b"\0"), "little")
            h = ((h ^ (w * 0xC2B2AE3D27D4EB4F & m)) * 0x9E3779B185EBCA87) & m
            h ^= h >> 32
        h ^= h >> 33
        h = (h * 0xFF51AFD7ED558CCD) & m
        h ^= h >> 33
        h = (h * 0xC4CEB9FE1A85EC53) & m
        return h ^ (h >> 33)

    def test_hash64_matches_the_per_value_loop(self):
        rng = np.random.default_rng(1)
        values = ["", "a", "a\x00", "abcdefgh", "abcdefgh\x00", "ü€𝄞",
                  None] + ["".join(chr(97 + c) for c in rng.integers(
                      0, 26, rng.integers(0, 60))) for _ in range(300)]
        arr = pa.array(values)
        want = [0 if v is None else self._py_hash64(v.encode("utf-8"))
                for v in values]
        for view in (arr, arr.cast(pa.large_string()),
                     pa.chunked_array([arr.slice(0, 5), arr.slice(5)]),
                     arr.dictionary_encode()):
            assert strings.hash64(view).tolist() == want
        assert strings.hash64(arr.slice(3, 40)).tolist() == want[3:43]

    def test_dictionary_hashes_are_the_lane_hashes(self):
        """The per-entry hashes of a dictionary are the lane's row
        hashes of the same strings; other entries hash their str()."""
        from deequ_tpu.sketches.hll import dictionary_hash_pairs

        entries = np.asarray(["a", None, "bc", "ü" * 9], dtype=object)
        h1, h2 = dictionary_hash_pairs(entries)
        w = strings.hash64(pa.array(list(entries)))
        assert h1.tolist() == (w & np.uint64(0xFFFFFFFF)).tolist()
        assert h2.tolist() == (w >> np.uint64(32)).tolist()
        assert h1[1] == h2[1] == 0
        got = dictionary_hash_pairs(np.asarray([1, 2.5], dtype=object))
        want = strings.hash_pairs(pa.array(["1", "2.5"]))
        assert [a.tolist() for a in got] == [a.tolist() for a in want]

    @pytest.mark.parametrize("pattern", [
        PATTERN_EMAIL, ID_PATTERN, PATTERN_SSN, r"\d{3}", "^$"])
    def test_regex_plane_matches_python_re(self, pattern):
        values = ["a@b.com", "x y@z", "ABCDEFGHABCDEFGH", "ABCDEFGHABCDEFGHA",
                  "123-45-6789", "000-12-3456", "", None, "ünï@cödé.de",
                  "12"]
        prog = re.compile(pattern)
        want = [v is not None and prog.search(v) is not None for v in values]
        assert strings.regex_plane(pa.array(values), pattern).tolist() == want

    @pytest.mark.parametrize("pattern", [
        PATTERN_EMAIL, ID_PATTERN, r"^\d+$", r"\w+$", r"\s", r"\S+$",
        r"\bx\b", r"[a[:digit:]]", r"a{,2}b", r"(?i)straße", r"(?i)^k$",
        r"^.$", r"(a)\1"])
    def test_regex_plane_reads_as_python_re(self, pattern):
        r"""Where RE2 reads a pattern or a value otherwise than ``re``
        (``$`` before a trailing newline, Unicode ``\d``, ``\w``,
        ``\s``, ``\b`` and case folding, POSIX classes, ``{,n}``,
        backreferences), the plane still gives ``re.search``'s
        answer."""
        values = ["a@b.com", "a@b.com\n", "ABCDEFGHABCDEFGH\n",
                  "ABCDEFGHABCDEFGH", "123", "123\n", "١٢٣", "12٣", "abc",
                  "ab\u00e9", "\u00e9", "x", "a\x0bb", "a\x1cb", "a\u00a0b",
                  "a b", "élan x ünï", "1", ":", "b", "aab", "aaab", "a]", "5", "STRASSE",
                  "straße", "K", "\u212a", "k\n", "aa", "", None]
        prog = re.compile(pattern)
        want = [v is not None and prog.search(v) is not None for v in values]
        assert strings.regex_plane(pa.array(values), pattern).tolist() == want

    @pytest.mark.parametrize("lane", [False, True])
    def test_pattern_match_reads_as_row_level_results(self, lane):
        """PatternMatch over dictionary codes or a lane's planes, and
        its row-level results, give ``re.search``'s answer on values
        where RE2 would not."""
        from deequ_tpu.checks.check import Check, CheckLevel
        from deequ_tpu.verification.suite import VerificationSuite

        values = ["a@b.com", "a@b.com\n", "١٢٣", "123", "123\n", "x",
                  None, "ab\u00e9"] * 4
        t = pa.table({"s": values})
        patterns = [PATTERN_EMAIL, r"^\d+$", r"\w$"]
        with config.configure(dict_delta_capacity=4 if lane else 1 << 16):
            ds = Dataset.from_arrow(t)
            check = Check(CheckLevel.ERROR, "re")
            for p in patterns:
                check = check.has_pattern("s", p, lambda v: True)
            result = VerificationSuite().on_data(ds).add_check(
                check).run()
            rows = result.row_level_results_as_dataset().table
        assert ds.string_lane("s") == lane
        for p in patterns:
            prog = re.compile(p)
            hits = [v is not None and prog.search(v) is not None
                    for v in values]
            metric = result.metrics[PatternMatch("s", p)]
            assert metric.value.get() == sum(hits) / len(values), p
        got = sorted(rows.column(i).to_pylist()
                     for i in range(rows.num_columns))
        want = sorted([v is not None and prog.search(v) is not None
                       for v in values]
                      for prog in map(re.compile, patterns))
        assert got == want

    @pytest.mark.parametrize("like", ["a.b%", "%(x)_", "50%% off", "[a]*",
                                      "a b c", "%$^|{1}", "ü_%"])
    def test_like_regex_reads_alike_in_re_and_re2(self, like):
        """LIKE's regex is one RE2 accepts, and its plane gives
        ``re.search``'s answer."""
        from deequ_tpu.sql.predicate import _sql_like_to_regex

        pattern = _sql_like_to_regex(like)
        values = ["a.b", "axb", "a.bc", "(x)y", "50% off", "50%x off",
                  "[a]*", "[a]", "a b c", "$^|{1}", "x$^|{1}", "üx", "ü",
                  None]
        prog = re.compile(pattern)
        want = [v is not None and prog.search(v) is not None for v in values]
        assert strings.regex_plane(pa.array(values), pattern).tolist() == want

    def test_classify_matches_classify_string(self):
        values = ["1", "-12", "+7", "1.5", ".5", "5.", "1e5", "1.5E-3",
                  "true", "FALSE", "TrUe", "truex", " 1", "1 ", "12\n",
                  "1.5\n", "true\n", "١٢٣", "x", "", "+", "-.5e1", "1e",
                  None]
        want = [0 if v is None else classify_string(v) for v in values]
        assert strings.classify(pa.array(values)).tolist() == want


class TestPlansAndStates:
    def test_lane_decision_is_the_same_rule_everywhere(self, table, shards):
        resident = Dataset.from_arrow(table)
        streamed = Dataset.from_parquet(shards)
        for c in ("c_customer_id", "c_email_address", "c_first_name",
                  "c_salutation", "c_customer_sk"):
            assert resident.string_lane(c) == streamed.string_lane(c)
        assert resident.string_lane("c_customer_id")
        assert not resident.string_lane("c_first_name")
        assert not resident.string_lane("c_customer_sk")
        # a lane is too big for any dictionary cap at or under capacity
        assert streamed.dictionary_size_within("c_email_address", 120) is None

    def test_table_without_strings_plans_as_before(self, monkeypatch):
        """No string column: nothing probes a lane, the plan-cache
        tokens carry the plain kinds, and the scan bakes in the same
        register build."""
        from deequ_tpu.analyzers.base import make_cache_token
        from deequ_tpu.sketches import hll

        def refuse(*args, **kwargs):
            raise AssertionError("a table without strings probed a lane")

        monkeypatch.setattr(strings, "lane_probe", refuse)
        rng = np.random.default_rng(2)
        ds = Dataset.from_arrow(pa.table({
            "k": pa.array(rng.integers(0, 1000, 5000)),
            "x": pa.array(rng.random(5000)),
        }))
        analyzers = [ApproxCountDistinct("k"), ApproxCountDistinct("x"),
                     Uniqueness(["k"]), Completeness("x")]
        records = []
        get_telemetry().add_span_sink(records.append)
        try:
            ctx = AnalysisRunner.do_analysis_run(ds, analyzers)
        finally:
            get_telemetry().remove_span_sink(records.append)
        assert all(ctx.metric(a).value.is_success for a in analyzers)
        assert make_cache_token(ApproxCountDistinct("k"), ds) == (
            repr(ApproxCountDistinct("k")), (("k", "Integral"),))
        first = [r for r in records if r["name"] == "scan:first_step"]
        assert first and all(r["attributes"]["hll_path"]
                             == hll.register_build_path() for r in first)
        assert not ds.__dict__.get("_string_lanes")

    def test_version_2_string_hll_state_is_refused(self, tmp_path,
                                                   monkeypatch):
        """The string hash changed from blake2b-8: a v2 state's string
        registers sit elsewhere, so loading one must fail."""
        assert STATE_FORMAT_VERSIONS["ApproxCountDistinctState"] == 3
        ds = Dataset.from_arrow(pa.table({"s": ["a", "b", "c"]}))
        analyzer = ApproxCountDistinct("s")
        monkeypatch.setitem(
            STATE_FORMAT_VERSIONS, "ApproxCountDistinctState", 2)
        AnalysisRunner.do_analysis_run(
            ds, [analyzer],
            save_states_with=FileSystemStateProvider(str(tmp_path)))
        monkeypatch.setitem(
            STATE_FORMAT_VERSIONS, "ApproxCountDistinctState", 3)
        with pytest.raises(TypeError, match="format v2"):
            FileSystemStateProvider(str(tmp_path)).load(analyzer)

    def test_lane_predicate_outside_its_planes_reads_the_dictionary(self):
        """A predicate that reads a lane column other than through its
        planes (an ordering, a string function, a comparison of two
        columns) gives that column dictionary codes, as before lanes,
        and answers as Arrow does; its planes-only neighbours keep the
        lane."""
        rng = np.random.default_rng(5)
        n = 90_000
        names = [f"{chr(65 + c)}name{i}" for i, c in
                 enumerate(rng.integers(0, 26, n))]
        other = [v if k else f"x{i}" for i, (v, k) in
                 enumerate(zip(names, rng.random(n) < 0.5))]
        names[7] = "x"
        t = pa.table({"name": names, "other": other})
        ds = Dataset.from_arrow(t)
        assert ds.string_lane("name") and ds.string_lane("other")
        checks = {
            "name >= 'M'": pc.greater_equal(t["name"], "M"),
            "UPPER(name) = 'X'": pc.equal(pc.utf8_upper(t["name"]), "X"),
            "name = other": pc.equal(t["name"], t["other"]),
            "name = 'x' OR LENGTH(other) > 8": pc.or_(
                pc.equal(t["name"], "x"),
                pc.greater(pc.utf8_length(t["other"]), 8)),
        }
        analyzers = [Compliance(e, e) for e in checks]
        ctx = AnalysisRunner.do_analysis_run(ds, analyzers)
        for a, want in zip(analyzers, checks.values()):
            assert ctx.metric(a).value.get() == (
                np.count_nonzero(want.to_numpy(zero_copy_only=False)) / n
            ), a.predicate

    def test_kept_lane_state_reloads_after_the_dataset_is_gone(
            self, table, want, tmp_path):
        """A run that persists states groups a lane column on the host,
        in the traversal its metric comes from: the saved state holds
        the values, and reloads and merges with the source gone."""
        import gc

        analyzers = [Uniqueness(["c_email_address"]),
                     CountDistinct(["c_customer_id"])]
        provider = FileSystemStateProvider(str(tmp_path))
        ds = Dataset.from_arrow(table)
        ctx = AnalysisRunner.do_analysis_run(
            ds, analyzers, save_states_with=provider)
        assert ds.string_lane("c_email_address")
        for a in analyzers:
            assert ctx.metric(a).value.get() == want[str(a)], a
        del ds, ctx
        gc.collect()
        for a in analyzers:
            state = FileSystemStateProvider(str(tmp_path)).load(a)
            assert a.compute_metric_from_state(state).value.get() == (
                want[str(a)]), a
        state = FileSystemStateProvider(str(tmp_path)).load(analyzers[1])
        assert sorted(state.keys[:, 0].tolist()) == sorted(
            table.column("c_customer_id").to_pylist())
        # merged with itself: every id seen twice, none once
        merged = AnalysisRunner.run_on_aggregated_states(
            Dataset.from_arrow(table.slice(0, 0)).schema,
            [Uniqueness(["c_customer_id"]), analyzers[1]],
            [FileSystemStateProvider(str(tmp_path))] * 2)
        assert merged.metric(analyzers[1]).value.get() == ROWS

    def test_lane_state_refuses_to_give_values(self, table):
        """The device grouping of a lane keeps counts only: asking its
        state for values fails plainly instead of re-reading a source."""
        from deequ_tpu.analyzers.grouping import FrequencyPlan, compute_frequencies
        from deequ_tpu.analyzers.spill import LaneFrequencies

        freq = compute_frequencies(
            Dataset.from_arrow(table),
            FrequencyPlan(("c_customer_id",), None, False))
        assert isinstance(freq, LaneFrequencies)
        assert freq.num_groups == ROWS
        with pytest.raises(TypeError, match="counts, not values"):
            freq.keys
