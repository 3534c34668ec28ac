"""Median device time of the decode step program (``jit_decode_step`` on
the ``XLA Modules`` line) in the traced window (ms)."""
import spans


def read(run):
    return spans.program_ms(spans.of_run(run), "jit_decode_step")
