"""Least time of the traced decode steps' latent-attention calls (live
positions only, one call a layer) over the ``mla_decode`` kernel's summed
device time in the trace (%)."""
import work
from work import mla_decode


def read(run):
    if run.traced is None or run.peaks is None:
        return None
    kernel_s = run.traced.kernel_s.get("mla_decode", 0.0)
    if kernel_s <= 0:
        return None
    s = run.sizes
    least = 0.0
    for st in run.traced_steps:
        if st.decode:
            ops, nb = mla_decode.work([c - 1 for c in st.decode], s.heads,
                                      s.rank, s.rope)
            least += work.least_time(ops, nb, run.peaks)[0] * s.layers
    return 100.0 * least / kernel_s if least else None
