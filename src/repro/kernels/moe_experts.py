"""SwiGLU experts of an MoE layer, gated and summed, in one Mosaic kernel.

For tokens x (T, D), gates (T, E) and E SwiGLU experts (wg, wu: (E, D,
F); wd: (E, F, D)) it returns ``sum_e gates[:, e] * (silu(x wg_e) * (x
wu_e)) wd_e`` (T, D) in float32: every expert over every token, a token's
gate being 0 for the experts it did not choose (drop-free dispatch, see
``models/moe.py``); shared experts are the same sum with gates of 1. The
grid walks (expert, F tile); each step reads one (D, bf) tile of wg and
wu and one (bf, D) tile of wd, so each expert's weights cross HBM once a
call, and the float32 (T, D) sum stays in VMEM for the whole call. The
projections and the SwiGLU product round to the activations' dtype; the
sum is left in float32 for the caller to round once.

The custom call is named by the caller: ``moe_experts`` for a decode
step's routed experts (read by the benchmark's ``bench/work/moe_ffn.py``
in the device trace), ``moe_experts_chunk`` for a prompt chunk's, and
``moe_shared`` / ``moe_shared_chunk`` for the shared experts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

EXPERTS_NAME = "moe_experts"
CHUNK_NAME = "moe_experts_chunk"
SHARED_NAME = "moe_shared"
_LANES = 128


def moe_experts_ref(x, gates, wg, wu, wd):
    """The kernel's sum in plain XLA (the oracle of the kernel's tests)."""
    f32 = jnp.float32
    g = jnp.einsum("td,edf->etf", x, wg, preferred_element_type=f32)
    u = jnp.einsum("td,edf->etf", x, wu, preferred_element_type=f32)
    h = (jax.nn.silu(g.astype(x.dtype).astype(f32))
         * u.astype(x.dtype).astype(f32)).astype(x.dtype)
    y = jnp.einsum("etf,efd->etd", h, wd, preferred_element_type=f32)
    return jnp.einsum("etd,te->td", y, gates.astype(f32),
                      precision=jax.lax.Precision.HIGHEST)


def _kernel(layer_ref, x_ref, g_ref, wg_ref, wu_ref, wd_ref, o_ref):
    del layer_ref                       # used by the index maps only
    e, j = pl.program_id(0), pl.program_id(1)

    @pl.when((e == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    f32 = jnp.float32
    x = x_ref[...]
    dt = x.dtype
    g = jnp.dot(x, wg_ref[0], preferred_element_type=f32).astype(dt)
    u = jnp.dot(x, wu_ref[0], preferred_element_type=f32).astype(dt)
    h = (jax.nn.silu(g.astype(f32)) * u.astype(f32)).astype(dt)   # (T, bf)
    y = jnp.dot(h, wd_ref[0], preferred_element_type=f32)         # (T, D)
    o_ref[...] += g_ref[0] * y


@functools.partial(jax.jit, static_argnames=("name", "interpret"))
def moe_experts_pallas(x, gates, wg, wu, wd, layer=0,
                       name: str = EXPERTS_NAME, interpret: bool = False):
    """The kernel. x (T, D), gates (T, E) float32, wg/wu ([L,] E, D, F),
    wd ([L,] E, F, D) -> (T, D) float32. Weights stacked over layers are
    read at ``layer`` where they lie. ``name`` names the custom call."""
    if wg.ndim == 3:
        wg, wu, wd = wg[None], wu[None], wd[None]
    t, d = x.shape
    _, e, _, f = wg.shape
    bf = _LANES if f % _LANES == 0 else f
    gate_cols = jnp.transpose(gates.astype(jnp.float32))[:, :, None]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, f // bf),
        in_specs=[
            pl.BlockSpec((t, d), lambda ie, j, lr: (0, 0)),
            pl.BlockSpec((1, t, 1), lambda ie, j, lr: (ie, 0, 0)),
            pl.BlockSpec((None, 1, d, bf), lambda ie, j, lr: (lr[0], ie, 0, j)),
            pl.BlockSpec((None, 1, d, bf), lambda ie, j, lr: (lr[0], ie, 0, j)),
            pl.BlockSpec((None, 1, bf, d), lambda ie, j, lr: (lr[0], ie, j, 0)),
        ],
        out_specs=pl.BlockSpec((t, d), lambda ie, j, lr: (0, 0)),
    )
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name=name)(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), x, gate_cols,
        wg, wu, wd)


def moe_experts(x, gates, wg, wu, wd, name: str = EXPERTS_NAME, layer=None):
    """The experts' gated sum (float32): the kernel named ``name`` on a
    TPU, XLA elsewhere. With ``layer``, the weights are stacked over
    layers and this call's are at ``layer``."""
    if jax.default_backend() == "tpu":
        return moe_experts_pallas(x, gates, wg, wu, wd,
                                  0 if layer is None else layer, name=name)
    if layer is not None:
        wg, wu, wd = wg[layer], wu[layer], wd[layer]
    return moe_experts_ref(x, gates, wg, wu, wd)
