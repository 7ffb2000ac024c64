"""Device time per join of the ops that ``stages.json`` maps to
``refine`` (compaction and the exact refinement), in milliseconds."""


def read(ctx):
    t = ctx["stage_s"].get("refine")
    if t is None or not ctx.get("units"):
        return None
    return 1000.0 * t / ctx["units"]
