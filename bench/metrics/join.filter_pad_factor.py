"""Bytes the filter's bucket programs gather over the bytes the filter
needs, per join: ``filter_gather_bytes`` (the program's count: each
bucket's padded rows times the sum of its power-of-two list widths, 8
bytes a slot) over ``filter_bytes`` (``harness.work``: the unpadded A and
F lists of both objects of every MBR candidate). It takes in the frame's
excess rows, each bucket's row padding and each row's width padding;
``None`` where the ``JoinStats`` carry no program counters."""


def read(ctx):
    stats = ctx.get("stats")
    if (not stats or not ctx.get("filter_bytes")
            or not all("counters" in s["extra"] for s in stats)):
        return None
    gathered = sum(s["extra"]["counters"].get("filter_gather_bytes", 0)
                   for s in stats) / len(stats)
    return gathered / ctx["filter_bytes"]
