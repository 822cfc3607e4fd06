import bisect

from perfbench import trace as tr
from perfbench.device import peaks
from perfbench.flops import decode_step_bytes


def read(ctx):
    """The least time the decode steps in the trace could take (weights
    once + live keys and values once, over the chip's memory bandwidth)
    over the time the device was busy inside those steps, %. The decode
    step is bound by bytes, not FLOPs, at these batch sizes."""
    if ctx.device["platform"] == "cpu":
        # A share of a chip's peak is never reported from a CPU run.
        return None
    t = ctx.trace
    if t is None or t["offset_ns"] is None:
        return None
    planes = tr.device_planes(t["raw"])
    if not planes:
        return None
    merged = tr.busy_intervals(tr.op_events(planes[0]))
    if not merged:
        return None
    lo, hi = merged[0][0], merged[-1][1]
    t0 = ctx.record["t0_monotonic"]
    by_rid = {r["rid"]: r for r in ctx.record["requests"]
              if r["rid"] is not None}
    bandwidth = peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    least_s, windows = 0.0, []
    for s in ctx.spans:
        if s.get("kind") != "span" or s.get("name") != "decode_step":
            continue
        start = s["ts"] * 1e9 + t["offset_ns"]
        end = start + s["dur"] * 1e9
        if start < lo or end > hi:
            continue
        live = 0
        for rid in s["rids"]:
            r = by_rid[rid]
            live += r["prompt_len"] + bisect.bisect_left(
                r["token_s"], s["ts"] - t0
            )
        least_s += decode_step_bytes(ctx.config, live) / bandwidth
        windows.append((start, end))
    busy_s = tr.busy_inside(merged, sorted(windows)) / 1e9
    if not windows or busy_s <= 0:
        return None
    return 100.0 * least_s / busy_s
