"""Whole-step model FLOP utilization: model FLOPs per token (flops.py) times
the traced window's tokens per second, over chips times the bf16 peak."""


def read(r):
    return 100.0 * r.flops_per_token * r.tokens_per_s / (
        r.chips * r.peak["bf16_flops_per_s"])
