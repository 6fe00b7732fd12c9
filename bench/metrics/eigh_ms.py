"""eigh_ms: device time per fit of the spectral factor's
eigendecomposition, on the device that spends the most on it (ms)."""


def read(summary):
    times = [d["layers"]["eigh"] for d in summary["devices"].values()
             if "eigh" in d["layers"]]
    return 1e3 * max(times) / summary["fits"] if times else None
