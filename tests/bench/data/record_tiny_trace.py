"""Records ``tiny.xplane.pb`` (the trace ``test_bench_reduce.py`` reads) on
one TPU chip. Run from the repository root:

    python3 tests/bench/data/record_tiny_trace.py <output dir>

Three rounds of a fused-LUT call and a paged flash-decode call as the
serving steps make them, a device read, a host pause inside the engine's
"sample" phase name and a bf16 matmul, all inside a ``bench.window`` span.
In the committed file the checkout's path in the source locations the
profiler records was replaced, byte for byte, by ``/work/repo/``.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.flash_decode import flash_decode_paged  # noqa: E402
from repro.kernels.fused_amm import vq_amm_pallas  # noqa: E402
from repro.obs.tracing import jax_annotation  # noqa: E402


def main(out: str) -> None:
    shutil.rmtree(out, ignore_errors=True)
    k = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(k[0], (8, 320, 8), jnp.bfloat16)
    z = jax.random.normal(k[1], (320, 16, 8), jnp.bfloat16)
    lut = jax.random.randint(k[2], (320, 16, 512), -127, 127).astype(jnp.int8)
    sc = jnp.ones((512,), jnp.float32)
    f = jax.jit(lambda x: vq_amm_pallas(x, z, lut, sc))
    pool = (33, 16, 4, 128)
    kp = jax.random.normal(k[3], pool, jnp.bfloat16)
    vp = jax.random.normal(k[4], pool, jnp.bfloat16)
    q = jax.random.normal(k[5], (2, 1, 4, 128), jnp.bfloat16)
    kn = jax.random.normal(k[6], (2, 1, 4, 128), jnp.bfloat16)
    phys = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    pos = jnp.asarray([100, 200], jnp.int32)
    g = jax.jit(lambda q: flash_decode_paged(q, kp, vp, kn, kn, phys, pos,
                                             impl="pallas"))
    m = jax.jit(lambda a: a @ a)
    a = jnp.ones((512, 512), jnp.bfloat16)
    jax.block_until_ready((f(x), g(q), m(a)))
    jax.profiler.start_trace(out)
    with jax_annotation("bench.window"):
        for _ in range(3):
            with jax_annotation("decode"):
                r = f(x)
                r2 = g(q)
            with jax_annotation("device_read"):
                jax.block_until_ready((r, r2))
            with jax_annotation("sample"):
                time.sleep(0.002)
            m(a).block_until_ready()
    jax.profiler.stop_trace()
    for p in glob.glob(os.path.join(out, "plugins", "profile", "*", "*")):
        print(p, os.path.getsize(p))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "tiny_trace")
