"""Generated tokens emitted in the window over the window's seconds."""
import measure


def read(run):
    return measure.out_tok_s(run)
