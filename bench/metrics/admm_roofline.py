"""admm_roofline: the least time the ADMM solves' required work takes
on this chip, over their device time per fit on the device that spends
the most on them (%).

The work is one machine's direction and CLIME solves, counted from the
shapes and the configuration's fixed iteration count
(:func:`bench.work.admm`), so every implementation is held to the same
number.  It is bound by compute; float32 products at HIGHEST take six
bf16 passes, which caps the share near 1/6.
"""


def read(summary):
    times = [d["layers"]["admm"] for d in summary["devices"].values()
             if "admm" in d["layers"]]
    if not times or max(times) <= 0:
        return None
    peaks = summary["peaks"]
    least, _ = summary["admm_work"].least_seconds(peaks.bf16_flops,
                                                  peaks.hbm_bytes_per_s)
    per_fit = max(times) / summary["fits"]
    return 100.0 * least * summary["machines_per_chip"] / per_fit
