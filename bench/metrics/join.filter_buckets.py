"""Filter bucket programs dispatched per join (``filter_buckets``, the
program's count in ``JoinStats.extra["counters"]``): one per power-of-two
width class and row chunk; ``None`` where the ``JoinStats`` carry no
program counters."""


def read(ctx):
    stats = ctx.get("stats")
    if not stats or not all("counters" in s["extra"] for s in stats):
        return None
    return sum(s["extra"]["counters"].get("filter_buckets", 0)
               for s in stats) / len(stats)
