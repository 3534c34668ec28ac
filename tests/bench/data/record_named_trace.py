"""Records ``named.xplane.pb`` (the trace ``test_bench_spans.py`` reads) on
one TPU chip. Run from the repository root:

    python3 tests/bench/data/record_named_trace.py <output dir>

A two-layer engine at Qwen1.5-4B's widths (4 slots of 256 positions,
pages of 16, random weights, ``Obs(jax_annotations=True)``) serves three
requests (prompts of 40, 20 and 8 tokens, 8 new tokens each) after a
warm-up; every step of that traffic runs inside a ``bench.window`` span,
with the harness's profiler options (annotations only, no Python frames).
In the committed file the checkout's path in the source locations the
profiler records was replaced, byte for byte, by ``/work/repo/``.
"""
import glob
import os
import shutil
import sys

sys.path.insert(0, "src")

import jax  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.lut import DENSE  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.obs import Obs  # noqa: E402
from repro.obs.tracing import jax_annotation  # noqa: E402
from repro.serve import Engine, Request  # noqa: E402


def main(out: str) -> None:
    shutil.rmtree(out, ignore_errors=True)
    model = Model(get_config("qwen1.5-4b").replace(num_layers=2))
    params = jax.jit(lambda k: model.init(k, DENSE))(jax.random.PRNGKey(0))
    eng = Engine(model, params, DENSE, batch_size=4, max_seq=256,
                 page_size=16, obs=Obs(jax_annotations=True))
    # compile every shape the traced steps use
    eng.run([Request(tokens=[1] * (eng.prefill_chunk + 1), max_new_tokens=3)])
    for n in (40, 20, 8):
        eng.submit(Request(tokens=list(range(2, 2 + n)), max_new_tokens=8))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax_annotation("bench.window"):
        while eng.scheduler.has_work:
            eng.step()
    jax.profiler.stop_trace()
    for p in glob.glob(os.path.join(out, "plugins", "profile", "*", "*")):
        print(p, os.path.getsize(p))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "named_trace")
