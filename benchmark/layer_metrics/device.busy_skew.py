"""Device: the busiest plane's busy time over the mean of the planes' busy
time (`per_device_busy_s` of the capture): 1.0 where the chips of one sharded
program work alike, and on one chip; towards n where one of n does all of it.
Nothing where the capture has no device plane."""


def read(run):
    busy = list((run.trace or {}).get("per_device_busy_s", {}).values())
    if not busy or not sum(busy):
        return None
    return max(busy) * len(busy) / sum(busy)
