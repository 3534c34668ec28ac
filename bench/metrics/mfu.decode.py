"""Dense-equivalent model FLOPs of the traced steps' tokens over the traced
window and the chip's bf16 peak (%)."""
import measure


def read(run):
    return measure.mfu(run)
