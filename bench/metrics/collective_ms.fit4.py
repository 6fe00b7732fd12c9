"""collective_ms.fit4: device time per fit of the all-reduce and
all-gather operations, on the device that spends the most on them (ms).
Read in the four-chip cell only."""


def read(summary):
    times = [d["layers"]["collective"] for d in summary["devices"].values()
             if "collective" in d["layers"]]
    return 1e3 * max(times) / summary["fits"] if times else None
