"""Share of the runs' wall spent traversing the source again after the
fused scan: the walls of the program's ``traverse:<kind>`` spans other
than ``traverse:fused`` (dictionary pre-passes, deferred spill and
group-by re-scans; one nested in another counts once), over the walls
of the runs, in %."""

from benchmark import spans as spansmod


def read(obs):
    got = spansmod.window(obs)
    if got is None:
        return None
    spans, runs = got
    if not any(s["name"].startswith("traverse:") for s in spans):
        return None
    rescans = spansmod.outermost(
        spans, lambda s: s["name"].startswith("traverse:")
        and s["name"] != "traverse:fused")
    wall = sum(r.end - r.start for r in runs)
    if wall <= 0:
        return None
    return 100.0 * sum(s["wall_s"] for s in rescans) / wall
