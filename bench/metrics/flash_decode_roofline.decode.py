"""Least time of the paged flash-decode calls (live positions only) over
the kernel's summed device time in the trace (%)."""
import measure


def read(run):
    return measure.flash_decode_roofline(run)
