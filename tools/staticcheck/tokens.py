"""Token-level discipline rules migrated from ``tools.telemetry_lint``.

Five rule families, unchanged in WHAT they flag (the token strings and
scopes are the originals, so ``tools.telemetry_lint``'s tuple API can
be rebuilt from these findings verbatim), changed in HOW: they run on
the shared :class:`~tools.staticcheck.core.SourceFile` token streams,
report through the framework's :class:`Finding`/waiver machinery, and
a malformed file surfaces as a ``tokenize-error`` finding instead of
crashing (the old scanner caught the nonexistent
``tokenize.TokenizeError`` — an AttributeError on first contact).

- ``telemetry-timing``: own-clock/own-trace NAME tokens outside
  ``deequ_tpu/telemetry/`` (docs/OBSERVABILITY.md); ``monotonic``
  too, except in ``deequ_tpu/service/``, where ``service-time``
  already bans it.
- ``oom-taxonomy``: ad-hoc OOM classification (``MemoryError`` NAMEs,
  allocator marker strings) outside ``engine/memory.py``.
- ``sync-discipline``: ``device_get``/``asarray`` in ``engine/``
  outside pack.py without a waiver (``# sync-ok:`` still honored).
- ``service-time`` / ``service-admission``: the PR 7 service rules —
  injected clocks only, engine entry only via the runner's admission
  layer (docs/SERVICE.md).
"""

from __future__ import annotations

import tokenize
from typing import Iterable, List, Sequence, Tuple

from tools.staticcheck.core import Analyzer, Finding, SourceFile, register

HOT_PATH_DIRS = (
    "deequ_tpu/engine",
    "deequ_tpu/data",
    "deequ_tpu/analyzers",
    "deequ_tpu/profiles",
    "deequ_tpu/verification",
    "deequ_tpu/sketches",
    "deequ_tpu/checks",
    "deequ_tpu/io",
    "deequ_tpu/utils",
    "deequ_tpu/service",
)

FORBIDDEN_NAMES = frozenset(
    {"perf_counter", "start_trace", "stop_trace", "TraceAnnotation"}
)
# a second clock is as much an own clock as perf_counter; the service
# layer's own rule (service-time) covers it there
FORBIDDEN_CLOCK_NAMES = frozenset({"monotonic"})
EXEMPT_PREFIX = "deequ_tpu/telemetry/"

FORBIDDEN_OOM_NAMES = frozenset({"MemoryError"})
FORBIDDEN_OOM_MARKERS = ("resource_exhausted", "out of memory")
OOM_EXEMPT_FILES = frozenset({"deequ_tpu/engine/memory.py"})

FORBIDDEN_SYNC_NAMES = frozenset({"device_get", "asarray"})
SYNC_HOT_PREFIX = "deequ_tpu/engine/"
SYNC_EXEMPT_FILES = frozenset({"deequ_tpu/engine/pack.py"})

SERVICE_PREFIX = "deequ_tpu/service/"
SERVICE_TIME_NAMES = frozenset({"sleep", "monotonic"})
SERVICE_ADMISSION_NAMES = frozenset(
    {
        "run_scan",
        "prepare_scan",
        "execute_plan",
        "_run_scan_resident",
        "_run_scan_streaming",
    }
)
SERVICE_TIME_ATTRS = frozenset(
    {"time", "sleep", "monotonic", "perf_counter"}
)


def _in_hot_path(rel: str) -> bool:
    return any(rel.startswith(d + "/") for d in HOT_PATH_DIRS)


def _service_hits(tokens: Sequence[tokenize.TokenInfo]) -> List[
    Tuple[int, str, str]
]:
    """(line, symbol, rule) hits for one service module: banned NAMEs
    plus the ``time.<attr>`` chain check, run over significant tokens
    only so comments/docstrings never flag."""
    out: List[Tuple[int, str, str]] = []
    significant = [
        tok
        for tok in tokens
        if tok.type
        in (tokenize.NAME, tokenize.OP, tokenize.NUMBER, tokenize.STRING)
    ]
    for i, tok in enumerate(significant):
        if tok.type != tokenize.NAME:
            continue
        if tok.string in SERVICE_TIME_NAMES:
            out.append((tok.start[0], tok.string, "service-time"))
        elif tok.string in SERVICE_ADMISSION_NAMES:
            out.append((tok.start[0], tok.string, "service-admission"))
        elif (
            tok.string == "time"
            and i + 2 < len(significant)
            and significant[i + 1].string == "."
            and significant[i + 2].type == tokenize.NAME
            and significant[i + 2].string in SERVICE_TIME_ATTRS
        ):
            out.append(
                (
                    tok.start[0],
                    f"time.{significant[i + 2].string}",
                    "service-time",
                )
            )
    return out


class TokenDisciplineAnalyzer(Analyzer):
    name = "tokens"
    rules = (
        "telemetry-timing",
        "oom-taxonomy",
        "sync-discipline",
        "service-time",
        "service-admission",
        "tokenize-error",
    )
    description = (
        "token-level hot-path discipline (timing/OOM/sync/service), "
        "migrated from tools.telemetry_lint"
    )

    def analyze(
        self, files: Sequence[SourceFile], root: str
    ) -> Iterable[Finding]:
        for sf in files:
            if not _in_hot_path(sf.rel):
                continue
            if sf.rel.startswith(EXEMPT_PREFIX):
                continue
            if sf.token_error is not None:
                yield Finding(
                    rule="tokenize-error",
                    path=sf.rel,
                    line=0,
                    message=f"cannot tokenize module: {sf.token_error}",
                    symbol="<tokenize error>",
                )
                continue
            oom_exempt = sf.rel in OOM_EXEMPT_FILES
            sync_checked = sf.rel.startswith(
                SYNC_HOT_PREFIX
            ) and sf.rel not in SYNC_EXEMPT_FILES
            timing_names = (
                FORBIDDEN_NAMES
                if sf.rel.startswith(SERVICE_PREFIX)
                else FORBIDDEN_NAMES | FORBIDDEN_CLOCK_NAMES
            )
            for tok in sf.tokens:
                if tok.type == tokenize.NAME and tok.string in timing_names:
                    yield Finding(
                        rule="telemetry-timing",
                        path=sf.rel,
                        line=tok.start[0],
                        message=(
                            f"ad-hoc timing/tracing token '{tok.string}' — "
                            "wall-clock attribution lives in "
                            "deequ_tpu/telemetry/"
                        ),
                        symbol=tok.string,
                    )
                elif (
                    tok.type == tokenize.NAME
                    and not oom_exempt
                    and tok.string in FORBIDDEN_OOM_NAMES
                ):
                    yield Finding(
                        rule="oom-taxonomy",
                        path=sf.rel,
                        line=tok.start[0],
                        message=(
                            f"ad-hoc OOM classification '{tok.string}' — "
                            "memory-pressure taxonomy lives in "
                            "engine/memory.py"
                        ),
                        symbol=tok.string,
                    )
                elif (
                    tok.type == tokenize.NAME
                    and sync_checked
                    and tok.string in FORBIDDEN_SYNC_NAMES
                ):
                    yield Finding(
                        rule="sync-discipline",
                        path=sf.rel,
                        line=tok.start[0],
                        message=(
                            f"engine-layer device sync '{tok.string}' "
                            "outside the packed epilogue (engine/pack.py)"
                        ),
                        symbol=tok.string,
                    )
                elif (
                    tok.type == tokenize.STRING
                    and not oom_exempt
                    and any(
                        marker in tok.string.lower()
                        for marker in FORBIDDEN_OOM_MARKERS
                    )
                ):
                    yield Finding(
                        rule="oom-taxonomy",
                        path=sf.rel,
                        line=tok.start[0],
                        message=(
                            "allocator-failure marker string — OOM "
                            "string-matching lives in engine/memory.py"
                        ),
                        symbol="<oom marker string>",
                    )
            if sf.rel.startswith(SERVICE_PREFIX):
                for line, symbol, rule in _service_hits(sf.tokens):
                    reason = (
                        "service modules run on injected clocks "
                        "(engine/deadline.py)"
                        if rule == "service-time"
                        else "service modules enter the engine via the "
                        "runner's admission layer only"
                    )
                    yield Finding(
                        rule=rule,
                        path=sf.rel,
                        line=line,
                        message=f"'{symbol}' in service layer — {reason}",
                        symbol=symbol,
                    )


register(TokenDisciplineAnalyzer())
