"""Planner time per run: the self time of the program's ``plan:*``
spans (``plan:prepare``, ``plan:lookup``; a span's wall less its child
spans, such as a dictionary pre-pass the planner starts), over the
runs, in ms."""

from benchmark import spans as spansmod


def read(obs):
    got = spansmod.window(obs)
    if got is None:
        return None
    spans, runs = got
    plans = [s for s in spans if s["name"].startswith("plan:")]
    if not plans:
        return None
    return 1e3 * sum(spansmod.self_s(s, spans) for s in plans) / len(runs)
