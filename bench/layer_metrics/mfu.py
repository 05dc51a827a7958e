"""The whole evaluation's share of the chips' peak, in percent: the
operations the window's evaluations need (``work.evaluation_flops``, from
level, p and box counts alone) over window x chips x peak FLOP/s.  It
bounds every kernel's roofline: work taken off a kernel still counts here."""
from bench import work


def read(inputs):
    flops = work.evaluation_flops(inputs.counts, inputs.level, inputs.p)
    window_s = inputs.trace.window_ns / 1e9
    return 100.0 * flops * inputs.evals / (
        window_s * inputs.chips * inputs.peak["flops_per_s"])
