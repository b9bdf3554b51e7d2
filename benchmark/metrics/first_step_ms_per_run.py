"""The first step of each fused scan per run: the walls of the
program's ``scan:first_step`` spans (the step's trace and compile or
program load where it has one, its dispatch where not; the span's
``retraces`` says which), over the runs, in ms."""

from benchmark import spans as spansmod


def read(obs):
    got = spansmod.window(obs)
    if got is None:
        return None
    spans, runs = got
    steps = [s for s in spans if s["name"] == "scan:first_step"]
    if not steps:
        return None
    return 1e3 * sum(s["wall_s"] for s in steps) / len(runs)
