"""Megabytes (1e6 bytes) the join gathers from the device per join
(``d2h_bytes``, counted by the program's one gather point); ``None`` where
the ``JoinStats`` carry no program counters."""


def read(ctx):
    stats = ctx.get("stats")
    if not stats or not all("counters" in s["extra"] for s in stats):
        return None
    return sum(s["extra"]["counters"].get("d2h_bytes", 0)
               for s in stats) / len(stats) / 1e6
