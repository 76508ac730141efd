"""Whole-step utilization: the operations of the live SGD steps that the
window's rounds folded (``bench.flops``), over the window's seconds, the
chips and each chip's bf16 peak. Masked steps past a client's own count
are not counted."""
UNIT = "%"


def read(facts):
    if not facts.peaks or facts.live_flops <= 0:
        return None
    return 100.0 * facts.live_flops / (
        facts.window_s * facts.chips * facts.peaks["flops_bf16"])
