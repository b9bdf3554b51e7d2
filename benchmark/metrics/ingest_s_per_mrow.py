"""Host ingest time per million rows run: the walls of the program's
``ingest:read``, ``ingest:decode`` and ``ingest:encode`` spans on every
thread (reader, pool workers, prefetcher, scan thread; one nested in
another counts once), over the rows the runs read, times 1e6."""

from benchmark import spans as spansmod

STAGES = ("ingest:read", "ingest:decode", "ingest:encode")


def read(obs):
    got = spansmod.window(obs)
    if got is None:
        return None
    spans, runs = got
    stages = spansmod.outermost(spans, lambda s: s["name"] in STAGES)
    rows = sum(r.rows for r in runs)
    if not stages or not rows:
        return None
    return sum(s["wall_s"] for s in stages) / rows * 1e6
