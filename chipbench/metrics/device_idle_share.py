"""``device_idle_share``: share of the window in which no operation ran
on the device, %: 1 - (union of the device's operation intervals) /
(traced window), from the profiler trace (``chipbench.trace``)."""


def read(record):
    red = record["trace"]
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
