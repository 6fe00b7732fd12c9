"""gram_ms: device time per fit of the sufficient statistics' Gram
kernel, on the device that spends the most on it (ms)."""


def read(summary):
    times = [d["layers"]["gram"] for d in summary["devices"].values()
             if "gram" in d["layers"]]
    return 1e3 * max(times) / summary["fits"] if times else None
