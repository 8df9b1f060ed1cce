"""Milliseconds from the end of one engine window program to the dispatch
of the next in the traced span (the control loop between engine windows:
poll, fleet pass, control, events, re-pack, and the engine call's own
table and carry work), mean over those gaps."""


def read(ctx):
    gaps = ctx["trace"]["window_gaps_s"]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
