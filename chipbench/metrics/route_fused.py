"""Share of the window's served read batches that the fused device
route served, from the program's route counts: the profiler tallies
``route.dense``, ``route.fused`` and ``route.host_pruned``, one
observation per served batch. A program without the tallies reads
nothing."""

ROUTES = ("route.dense", "route.fused", "route.host_pruned")


def read(ctx):
    b, a = ctx["before"]["stages"], ctx["after"]["stages"]
    n = {r: a.get(r, (0, 0.0))[0] - b.get(r, (0, 0.0))[0] for r in ROUTES}
    total = sum(n.values())
    if total <= 0:
        return None
    return 100.0 * n["route.fused"] / total
