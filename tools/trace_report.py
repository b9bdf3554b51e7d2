"""Render per-run trace waterfalls and critical-path decompositions
from a telemetry JSONL artifact.

Usage:

    python -m tools.trace_report runs.jsonl              # every trace
    python -m tools.trace_report runs.jsonl --run <id>   # one trace
    python -m tools.trace_report runs.jsonl --json       # machine form

The artifact is the ordinary telemetry JSONL (``configure(jsonl_path=
...)`` or per-host files concatenated); any span line carrying a
``trace_id`` participates. One trace = one submission's causal
timeline: the synthetic ``ticket`` root span (submit -> finished wall)
with queue_wait / coalesce_window / lease_wait / execute / engine
children — across processes, since spawn children stream their spans
back and replay re-roots them (docs/OBSERVABILITY.md "Tracing").

The critical-path decomposition attributes every span's SELF time
(wall minus children) to one of the fixed stages below, so the stage
seconds of a run sum to its root wall by construction — no stage
double-counts a nested child. A ``coalesced_scan`` link span (a
member's view of the host's superset scan) is resolved by descending
into the linked host subtree and apportioning the link's wall by the
host's own stage fractions.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Any, Dict, List, Optional, Tuple

from deequ_tpu.telemetry import read_jsonl

#: the fixed critical-path stages, in pipeline order
STAGES = (
    "queue_wait",
    "coalesce_window",
    "lease_wait",
    "compile",
    "scan",
    "finalize",
    "egress",
    "persist",
)

#: exact span-name -> stage attribution; names not listed fall through
#: to the prefix rules, then inherit their parent's stage
_STAGE_BY_NAME = {
    "queue_wait": "queue_wait",
    "coalesce_window": "coalesce_window",
    "lease_wait": "lease_wait",
    "scan:first_step": "compile",
    "egress": "egress",
    "persist": "persist",
    "ticket": "finalize",
    "execute": "finalize",
}


def _stage_for(name: str, parent_stage: str) -> str:
    stage = _STAGE_BY_NAME.get(name)
    if stage is not None:
        return stage
    if name.startswith(("pass:", "traverse:")):
        return "scan"
    if name.startswith("run:"):
        return "finalize"
    return parent_stage or "finalize"


def load_traces(
    records: List[Dict[str, Any]],
) -> Dict[str, List[Dict[str, Any]]]:
    """Span records grouped by trace_id, in file order (spans without a
    trace_id — untraced runs — are not part of any timeline)."""
    traces: Dict[str, List[Dict[str, Any]]] = {}
    for r in records:
        if r.get("type") != "span" or not r.get("trace_id"):
            continue
        traces.setdefault(str(r["trace_id"]), []).append(r)
    return traces


class _Tree:
    """Index of one trace's spans: children adjacency + the root."""

    def __init__(self, spans: List[Dict[str, Any]]):
        self.by_id: Dict[int, Dict[str, Any]] = {}
        for sp in spans:
            sid = sp.get("span_id")
            if isinstance(sid, int) and sid not in self.by_id:
                self.by_id[sid] = sp
        self.children: Dict[Optional[int], List[Dict[str, Any]]] = {}
        roots: List[Dict[str, Any]] = []
        for sp in self.by_id.values():
            parent = sp.get("parent_id")
            if parent in self.by_id and parent != sp.get("span_id"):
                self.children.setdefault(parent, []).append(sp)
            else:
                roots.append(sp)
        for kids in self.children.values():
            kids.sort(key=lambda s: s.get("started_at", 0.0))
        # the synthetic ticket root has parent None; tolerate torn
        # artifacts by falling back to the longest parentless span
        roots.sort(
            key=lambda s: (
                s.get("parent_id") is not None,
                -float(s.get("wall_s", 0.0)),
            )
        )
        self.root = roots[0] if roots else None
        self.orphans = roots[1:]

    def kids(self, sp: Dict[str, Any]) -> List[Dict[str, Any]]:
        return self.children.get(sp.get("span_id"), [])

    def self_s(self, sp: Dict[str, Any]) -> float:
        wall = float(sp.get("wall_s", 0.0))
        nested = sum(float(k.get("wall_s", 0.0)) for k in self.kids(sp))
        return max(0.0, wall - nested)


def _link_target(
    sp: Dict[str, Any], trees: Dict[str, "_Tree"]
) -> Optional[Tuple["_Tree", Dict[str, Any]]]:
    attrs = sp.get("attributes") or {}
    link_trace = attrs.get("link_trace_id")
    link_span = attrs.get("link_span_id")
    tree = trees.get(str(link_trace)) if link_trace else None
    if tree is None:
        return None
    target = tree.by_id.get(link_span)
    if target is None:
        return None
    return tree, target


def _accumulate(
    tree: _Tree,
    sp: Dict[str, Any],
    parent_stage: str,
    out: Dict[str, float],
    trees: Dict[str, _Tree],
) -> None:
    name = str(sp.get("name", ""))
    if name == "coalesced_scan":
        # a member's link onto the host's superset scan: apportion the
        # link's wall by the linked subtree's own stage fractions so
        # the member's timeline stays honest about WHERE the shared
        # wall went (all-scan when the host trace is not in the file)
        wall = float(sp.get("wall_s", 0.0))
        linked = _link_target(sp, trees)
        if linked is not None:
            host_tree, host_span = linked
            host_stages: Dict[str, float] = {}
            _accumulate(
                host_tree, host_span, "scan", host_stages, trees
            )
            total = sum(host_stages.values())
            if total > 0:
                for stage, value in host_stages.items():
                    out[stage] = (
                        out.get(stage, 0.0) + wall * value / total
                    )
                return
        out["scan"] = out.get("scan", 0.0) + wall
        return
    stage = _stage_for(name, parent_stage)
    out[stage] = out.get(stage, 0.0) + tree.self_s(sp)
    for kid in tree.kids(sp):
        _accumulate(tree, kid, stage, out, trees)


def decompose(
    trace_id: str, trees: Dict[str, _Tree]
) -> Dict[str, Any]:
    """One trace's critical-path stages: {stage: seconds} summing to
    the root wall, plus root metadata for reports."""
    tree = trees[trace_id]
    stages: Dict[str, float] = {}
    root = tree.root
    if root is None:
        return {"trace_id": trace_id, "wall_s": 0.0, "stages": {}}
    _accumulate(tree, root, "", stages, trees)
    for orphan in tree.orphans:
        _accumulate(tree, orphan, "", stages, trees)
    attrs = root.get("attributes") or {}
    return {
        "trace_id": trace_id,
        "run_id": attrs.get("run_id"),
        "tenant": attrs.get("tenant"),
        "status": attrs.get("status"),
        "wall_s": float(root.get("wall_s", 0.0)),
        "stages": {
            k: stages.get(k, 0.0)
            for k in STAGES
            if stages.get(k, 0.0) > 0.0
        },
    }


def dominant_stage(stages: Dict[str, float]) -> Tuple[str, float]:
    if not stages:
        return "finalize", 0.0
    name = max(stages, key=lambda k: stages[k])
    total = sum(stages.values())
    return name, (stages[name] / total if total > 0 else 0.0)


def _quantile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = min(len(ordered) - 1, max(0, math.ceil(q * len(ordered)) - 1))
    return ordered[idx]


def aggregate(decomps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fleet view across runs: p50/p99 wall, each attributed to the
    dominant stage of the run AT that quantile — the stage a capacity
    fix should target first."""
    walls = [d["wall_s"] for d in decomps]
    out: Dict[str, Any] = {"runs": len(decomps)}
    for label, q in (("p50", 0.5), ("p99", 0.99)):
        wall = _quantile(walls, q)
        at = min(
            decomps, key=lambda d: (abs(d["wall_s"] - wall), d["trace_id"])
        )
        stage, share = dominant_stage(at["stages"])
        out[label] = {
            "wall_s": wall,
            "dominant_stage": stage,
            "dominant_share": share,
        }
    out["stage_p50_s"] = {
        stage: _quantile([d["stages"].get(stage, 0.0) for d in decomps], 0.5)
        for stage in STAGES
    }
    out["stage_p99_s"] = {
        stage: _quantile([d["stages"].get(stage, 0.0) for d in decomps], 0.99)
        for stage in STAGES
    }
    return out


# -- rendering -------------------------------------------------------------


def _render_span(
    tree: _Tree,
    sp: Dict[str, Any],
    t0: float,
    depth: int,
    lines: List[str],
) -> None:
    offset = float(sp.get("started_at", t0)) - t0
    name = str(sp.get("name", "?"))
    process = sp.get("process")
    suffix = f"  [{process}]" if process else ""
    attrs = sp.get("attributes") or {}
    link = (
        f"  -> {attrs.get('link_trace_id')}"
        if name == "coalesced_scan" and attrs.get("link_trace_id")
        else ""
    )
    lines.append(
        f"  {'  ' * depth}{max(0.0, offset):8.3f}s "
        f"+{float(sp.get('wall_s', 0.0)):.3f}s  {name}{link}{suffix}"
    )
    for kid in tree.kids(sp):
        _render_span(tree, kid, t0, depth + 1, lines)


def render_trace(
    trace_id: str, trees: Dict[str, _Tree]
) -> str:
    tree = trees[trace_id]
    if tree.root is None:
        return f"trace {trace_id}: no spans"
    root = tree.root
    attrs = root.get("attributes") or {}
    head = f"trace {trace_id}"
    if attrs.get("run_id"):
        head += f"  run={attrs['run_id']}"
    if attrs.get("tenant"):
        head += f"  tenant={attrs['tenant']}"
    if attrs.get("status"):
        head += f"  status={attrs['status']}"
    lines = [head]
    t0 = float(root.get("started_at", 0.0))
    _render_span(tree, root, t0, 0, lines)
    for orphan in tree.orphans:
        _render_span(tree, orphan, t0, 0, lines)
    d = decompose(trace_id, trees)
    wall = d["wall_s"]
    covered = sum(d["stages"].values())
    lines.append(
        f"  critical path ({wall:.3f}s wall,"
        f" {100.0 * covered / wall if wall > 0 else 0.0:.0f}% attributed):"
    )
    for stage in STAGES:
        value = d["stages"].get(stage, 0.0)
        if value <= 0.0:
            continue
        share = 100.0 * value / wall if wall > 0 else 0.0
        lines.append(f"    {stage:<16} {value:9.3f}s  {share:5.1f}%")
    return "\n".join(lines)


def render_aggregate(decomps: List[Dict[str, Any]]) -> str:
    agg = aggregate(decomps)
    lines = [f"aggregate over {agg['runs']} traced run(s):"]
    for label in ("p50", "p99"):
        stat = agg[label]
        lines.append(
            f"  {label} wall {stat['wall_s']:.3f}s — dominant stage:"
            f" {stat['dominant_stage']}"
            f" ({100.0 * stat['dominant_share']:.0f}% of that run)"
        )
    lines.append(f"  {'stage':<16} {'p50':>9} {'p99':>9}")
    for stage in STAGES:
        p50 = agg["stage_p50_s"].get(stage, 0.0)
        p99 = agg["stage_p99_s"].get(stage, 0.0)
        if p50 <= 0.0 and p99 <= 0.0:
            continue
        lines.append(f"  {stage:<16} {p50:8.3f}s {p99:8.3f}s")
    return "\n".join(lines)


def _match(trace_id: str, tree: _Tree, wanted: str) -> bool:
    if trace_id == wanted or trace_id.startswith(wanted):
        return True
    root = tree.root
    if root is None:
        return False
    attrs = root.get("attributes") or {}
    return str(attrs.get("run_id", "")) == wanted


def render(
    records: List[Dict[str, Any]],
    run: Optional[str] = None,
    as_json: bool = False,
) -> str:
    traces = load_traces(records)
    trees = {tid: _Tree(spans) for tid, spans in traces.items()}
    selected = [
        tid
        for tid, tree in trees.items()
        if run is None or _match(tid, tree, run)
    ]
    if not selected:
        if run is not None:
            return f"no trace matching {run!r} in artifact"
        n_spans = sum(1 for r in records if r.get("type") == "span")
        return (
            f"no traced spans in artifact ({n_spans} untraced span(s))"
            " — was the service started with service_trace enabled?"
        )
    decomps = [decompose(tid, trees) for tid in selected]
    if as_json:
        payload: Dict[str, Any] = {"runs": decomps}
        if len(decomps) > 1:
            payload["aggregate"] = aggregate(decomps)
        return json.dumps(payload, indent=2, sort_keys=True)
    body = "\n\n".join(render_trace(tid, trees) for tid in selected)
    if len(decomps) > 1:
        body += "\n\n" + render_aggregate(decomps)
    return body


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        description="Render trace waterfalls and critical-path "
        "decompositions from a telemetry JSONL artifact"
    )
    parser.add_argument("path", help="telemetry JSONL file")
    parser.add_argument(
        "--run",
        default=None,
        help="render only the trace matching this trace_id (prefix) "
        "or submission run_id",
    )
    parser.add_argument(
        "--json", action="store_true", help="emit machine-readable JSON"
    )
    args = parser.parse_args(argv)
    try:
        records = read_jsonl(args.path)
    except OSError as exc:
        print(f"cannot read {args.path}: {exc}", file=sys.stderr)
        return 2
    print(render(records, run=args.run, as_json=args.json))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
