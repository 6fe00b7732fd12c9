"""fit_mfu: the whole fit's required flops over the traced window's
time per fit and the chips' bf16 peak (%).

The flops are the Gram matrix, both ADMM solves and the debias rounds
of every machine held, counted from the shapes
(:func:`bench.work.fit`); the eigendecomposition is left out.  It bounds
what the per-kernel rooflines can claim: a kernel taken off the path
leaves its roofline silent, but not this.
"""


def read(summary):
    if summary["fits"] <= 0 or summary["window_s"] <= 0:
        return None
    per_fit = summary["window_s"] / summary["fits"]
    flops = (summary["fit_work"].flops * summary["machines_per_chip"]
             * summary["chips"])
    return 100.0 * flops / (per_fit * summary["chips"]
                            * summary["peaks"].bf16_flops)
