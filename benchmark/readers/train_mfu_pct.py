"""Model FLOP/s utilisation: (6 N + attention) operations per token
(benchmark/counts.py) x tokens per second per chip of THIS run, over the
chip's bf16 peak.  Recomputed operations do not count."""


def read(ctx):
    rate = ctx.run["end_to_end"].get("train_tokens_per_s_per_chip")
    if rate is None or ctx.peaks is None:
        return None
    per_token = ctx.counts.train_flops_per_token(
        ctx.config, ctx.run["shapes"]["seq"])
    return 100.0 * per_token * rate / ctx.peaks["bf16_flops_per_s"]
