"""INDECISIVE pairs over MBR candidates, in percent, from the ``JoinStats``
of the traced joins: the share of candidates the filter leaves to
refinement."""


def read(ctx):
    if not ctx.get("stats"):
        return None
    cand = sum(s["n_candidates"] for s in ctx["stats"])
    if cand == 0:
        return None
    return 100.0 * sum(s["n_indecisive"] for s in ctx["stats"]) / cand
