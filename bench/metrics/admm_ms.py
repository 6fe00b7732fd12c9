"""admm_ms: device time per fit of the ADMM solves (the direction and
the CLIME columns), on the device that spends the most on them (ms)."""


def read(summary):
    times = [d["layers"]["admm"] for d in summary["devices"].values()
             if "admm" in d["layers"]]
    return 1e3 * max(times) / summary["fits"] if times else None
