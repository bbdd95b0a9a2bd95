"""Bytes the tick scan has to move, from the shapes of one call.

The scan is bound by memory, not by operations: its float64 arithmetic is
emulated on a TPU and uses no matrix unit, so its roofline is HBM bandwidth.
The count is the least traffic any implementation of the same work needs:
each input read once and each output written once, nothing for the carry.
It depends only on shapes, so it reads the same work whatever computes it.
"""

F64, I32, BOOL = 8, 4, 1
N_SCALARS = 22  # thresholds, clocks, power and SLO constants of a scenario


def scan_bytes(*, N: int, R: int, T: int, T60: int, S: int,
               keep_series: bool, keep_fire: bool) -> int:
    """Bytes read and written by one scan over N members, R rows, T ticks."""
    read = (N * R * T60 * F64          # occupancy on the 60 s grid
            + 2 * T * R * F64          # row-alive mask and budget scale
            + T * (2 * F64 + 2 * I32)  # tick time, interpolation weight,
                                       # grid index and tick number
            + R * F64 + N_SCALARS * F64)  # row budgets and scalars
    write = (N * R * I32               # brake counts
             + 2 * N * F64             # peak and mean power
             + N * S * R * 2 * F64)    # impact samples of both priorities
    if keep_fire:
        write += N * T * R * BOOL      # the brake plane
    if keep_series:
        write += N * T * (1 + R) * F64  # total and per-row power series
    return read + write


def roofline_pct(total_bytes: float, device_s: float,
                 hbm_bytes_per_s: float) -> float:
    """Share of the HBM roofline: the least time the bytes take at peak
    bandwidth over the device time they took, in percent."""
    return 100.0 * total_bytes / (device_s * hbm_bytes_per_s)
