"""Host time of the hashed-string lane per million rows run: the walls
of the program's ``ingest:strings`` spans on every thread (the ingest
workers that hash and build the planes; one nested in another counts
once), over the rows the runs read, times 1e6. Nothing where the
program has no such span."""

from benchmark import spans as spansmod


def read(obs):
    got = spansmod.window(obs)
    if got is None:
        return None
    spans, runs = got
    lane = spansmod.outermost(spans, lambda s: s["name"] == "ingest:strings")
    rows = sum(r.rows for r in runs)
    if not lane or not rows:
        return None
    return sum(s["wall_s"] for s in lane) / rows * 1e6
