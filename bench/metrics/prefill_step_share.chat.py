"""Share of the traced window's ``engine.step`` spans whose
``prefill_tokens`` is above 0: steps that carried a prompt chunk (%)."""
import spans


def read(run):
    return spans.prefill_step_share(spans.of_run(run))
