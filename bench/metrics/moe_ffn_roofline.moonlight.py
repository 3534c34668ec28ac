"""Least time of the routed experts' work in the traced window's decode
steps (``engine.step``'s ``moe_pairs`` and ``moe_experts_hit``) over the
``moe_experts`` kernel's summed device time in the trace (%)."""
import spans
import work
from work import moe_ffn


def read(run):
    if run.traced is None or run.peaks is None:
        return None
    kernel_s = run.traced.kernel_s.get("moe_ffn", 0.0)
    sp = spans.of_run(run)
    if kernel_s <= 0 or sp is None:
        return None
    s = run.sizes
    least = 0.0
    for a in sp.steps:
        if a.get("moe_pairs", 0) > 0:
            ops, nb = moe_ffn.work(a["moe_pairs"], a["moe_experts_hit"],
                                   s.d, s.expert_ff)
            least += work.least_time(ops, nb, run.peaks)[0]
    return 100.0 * least / kernel_s if least else None
