"""Peak device memory of the run, in GB (peak_bytes_in_use)."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
