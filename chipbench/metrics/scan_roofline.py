"""The scans' share of the HBM roofline: bytes from shapes over device
time at peak bandwidth, in percent."""

from chipbench.readers import scan_roofline


def read(run):
    return scan_roofline(run)
