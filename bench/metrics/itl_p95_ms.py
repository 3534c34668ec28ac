"""95th percentile of every gap between consecutive output tokens of the
window's requests (ms)."""
import measure


def read(run):
    return measure.pct(measure.itl_ms(run), 95)
