"""Rows routed off the device path per join (interval rows too wide for
the filter kernel, guard-band pairs escalated to host f64, compaction
lanes too long for the kernel), from ``JoinStats.extra["routed"]``."""


def read(ctx):
    if not ctx.get("stats"):
        return None
    total = sum(sum(s["extra"]["routed"].values()) for s in ctx["stats"])
    return total / len(ctx["stats"])
