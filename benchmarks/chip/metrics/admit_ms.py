"""Host milliseconds of the benchmark's own admission call
(``FleetController.admit_fleet``) at the start of each timeline."""


def read(ctx):
    admits = [t["admit_s"] for t in ctx["timelines"] if t["admit_s"] > 0]
    if not admits:
        return None
    return 1e3 * sum(admits) / len(admits)
