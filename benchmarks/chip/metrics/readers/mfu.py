"""Model FLOP/s utilization: tokens per second per chip (from the median
step, so that the profiler's own stalls in a traced run do not count) times
the operations a token requires, forward and backward with no recompute,
over the chip's peak."""

from ...flops import train_flops_per_token
from ...reference.gpt_reference import sizes
from ...stats import percentile


def read(ctx):
    steps = ctx.samples.get("step_ms")
    if not steps:
        return None
    cfg = ctx.model_config
    z = sizes(ctx.cell.config)
    z["d_ff"] = cfg.ffn_dim
    per_token = train_flops_per_token(z, cfg.max_seq_len, cfg.padded_vocab)
    tokens_per_s = ctx.scalars["tokens_per_step"] / (
        percentile(steps, 50.0) * 1e-3) / len(ctx.devices)
    return 100.0 * tokens_per_s * per_token / ctx.peaks["bf16_flops"]
