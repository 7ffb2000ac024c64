"""Host milliseconds per join in the filter's host-only steps, from
``JoinStats.extra["spans_s"]``: ``repro.filter.plan`` (list counts and
bucket planning) and ``repro.filter.args`` (each bucket's row offsets,
counts and widths, built in numpy). Neither step touches the device, so
a busy device does not leak into the reading: the bucket programs'
uploads, dispatch and lane scatter (``repro.filter.dispatch``), which
block while the device works, are left out. ``None`` where the
``JoinStats`` carry no program spans."""

SPANS = ("repro.filter.plan", "repro.filter.args")


def read(ctx):
    stats = ctx.get("stats")
    if not stats or not all("spans_s" in s["extra"] for s in stats):
        return None
    return 1000.0 * sum(s["extra"]["spans_s"].get(name, 0.0)
                        for s in stats for name in SPANS) / len(stats)
