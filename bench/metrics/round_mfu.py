"""Percent of the chip's bfloat16 peak that the window's required
operations would fill: three forward passes per training example of
every real pair, one forward pass per validation and test example of
every real eval row over all devices, nothing for padding or
recomputation (``bench/flops.py``), over the window's wall time. The
peak is bfloat16 because the rounds run at the default precision, where
the convolutions take bfloat16 operands."""


def read(ctx):
    total = sum(r["flops"] for r in ctx.rounds)
    return 100.0 * total / (ctx.window_s * ctx.peak("bf16_flops_per_s"))
