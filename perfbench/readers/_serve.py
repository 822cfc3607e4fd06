"""Shared by the serving readers: which requests count."""

from perfbench.stats import due_in_window


def measured(ctx) -> list:
    """Requests whose latencies count: open loop, those due in the
    window; closed loop, those submitted in it. Only requests that
    finished have latencies; the others are counted as failed."""
    rec = ctx.record
    if rec.get("kind") != "serve":
        return []
    reqs = rec["requests"]
    inside = due_in_window([r["due_s"] for r in reqs], rec["seconds"])
    return [reqs[i] for i in inside if reqs[i]["submit_s"] is not None]


def finished(ctx) -> list:
    return [r for r in measured(ctx)
            if r["finish_reason"] in ("length", "eos") and r["token_s"]]
