"""Share of the traced window in which no operation ran on the device (%)."""
import measure


def read(run):
    return measure.idle_share(run)
