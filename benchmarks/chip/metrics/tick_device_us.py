"""Device microseconds of the engine's window program per fleet tick:
the window programs' device time in the traced span over the fleet ticks
they simulated."""


def read(ctx):
    wp = ctx["trace"]["window_program"]
    if not wp["count"]:
        return None
    return wp["seconds"] / (wp["count"] * ctx["window_ticks"]) * 1e6
