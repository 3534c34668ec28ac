"""Median device time of the prefill chunk program (``jit_prefill_chunk``
on the ``XLA Modules`` line) in the traced window (ms)."""
import spans


def read(run):
    return spans.program_ms(spans.of_run(run), "jit_prefill_chunk")
