"""Host milliseconds per join in the span ``repro.mbr.frame``: the grid
hash that builds the candidate pair frame, from
``JoinStats.extra["spans_s"]``; ``None`` where the ``JoinStats`` carry no
program spans."""


def read(ctx):
    stats = ctx.get("stats")
    if not stats or not all("spans_s" in s["extra"] for s in stats):
        return None
    return 1000.0 * sum(s["extra"]["spans_s"].get("repro.mbr.frame", 0.0)
                        for s in stats) / len(stats)
