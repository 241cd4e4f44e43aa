"""The whole round's share of the chips' peak: the FLOPs a round needs,
counted from shapes (``flops.round_flops``), times the rounds of the traced
window, over the traced window's length times the peak of the chips used."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0 or not r.window_rounds:
        return None
    done = r.flops_per_round * r.window_rounds
    return 100.0 * done / (r.trace.window_s * r.peak_flops * r.trace.n_devices)
