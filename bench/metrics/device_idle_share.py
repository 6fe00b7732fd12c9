"""device_idle_share: the share of the traced window in which no
operation ran on the device, on the device idle the longest (%)."""


def read(summary):
    devices = summary["devices"].values()
    if not devices or summary["window_s"] <= 0:
        return None
    return 100.0 * max(d["idle_s"] for d in devices) / summary["window_s"]
