"""The filter stage's share of its byte roofline, in percent: the least
time the chip needs to read the bytes the filter needs for one join (the
unpadded A and F interval lists of both objects of every MBR candidate,
``harness.work``) at the HBM peak, over the device time per join of the
ops that ``stages.json`` maps to ``filter``. No int32 compare peak is
published, so the bound is the byte bound."""


def read(ctx):
    t = ctx["stage_s"].get("filter", 0.0)
    if t <= 0 or not ctx.get("units"):
        return None
    t_min = ctx["filter_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * t_min / (t / ctx["units"])
