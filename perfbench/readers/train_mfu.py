from perfbench.device import peaks
from perfbench.flops import bert_train_flops_per_sample


def read(ctx):
    """Model FLOP/s utilisation: FLOPs the forward and backward passes
    need per sample x samples/s over (chips x the chip's bf16 peak), %."""
    if ctx.device["platform"] == "cpu":
        # A share of a chip's peak is never reported from a CPU run.
        return None
    rec = ctx.record
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    rate = len(rec["steps"]) * rec["batch"] / rec["window_s"]
    flops = bert_train_flops_per_sample(ctx.config, rec["seq_len"])
    peak = peaks(ctx.device["kind"])["bf16_flops_per_s"]
    return 100.0 * flops * rate / (ctx.device["count"] * peak)
