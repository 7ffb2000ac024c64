"""Megabytes (1e6 bytes) the join uploads to the device per join
(``h2d_bytes``, counted by the program's one upload point); ``None`` where
the ``JoinStats`` carry no program counters."""


def read(ctx):
    stats = ctx.get("stats")
    if not stats or not all("counters" in s["extra"] for s in stats):
        return None
    return sum(s["extra"]["counters"].get("h2d_bytes", 0)
               for s in stats) / len(stats) / 1e6
