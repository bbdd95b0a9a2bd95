"""Device seconds of one unit's scans, from the profiler trace."""

from chipbench.readers import scan_device_s


def read(run):
    return scan_device_s(run)
