"""LUTBoost — the paper's lightweight multistage model converter (§V).

Stage ① swap linears for LUT operators and initialise centroids by per-
          subspace k-means over calibration activations;
Stage ② train *centroids only* (weights frozen) — fast convergence to a
          faithful representation of each layer's input distribution;
Stage ③ joint fine-tune of centroids + weights.

This module provides the conversion utilities and the stage bookkeeping; the
actual optimisation loop lives in ``repro.train.trainer`` (big models) and in
``benchmarks/table2_lutboost.py`` (paper-style small-model studies).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .codebook import kmeans_codebook
from .lut import QuantConfig, precompute_layer, strip_for_inference

# ---------------------------------------------------------------------------
# Stage ①: calibration capture + k-means init
# ---------------------------------------------------------------------------

_CAPTURE: Optional[Dict[int, np.ndarray]] = None


@contextlib.contextmanager
def capture_activations():
    """Context manager that records the input of every LutLinear, keyed by
    ``id(params['z'])``. Must run *eagerly* (outside jit) so array object
    identity is stable — conversion is a one-off offline step, so this costs
    nothing at training time."""
    global _CAPTURE
    prev = _CAPTURE
    _CAPTURE = {}
    try:
        yield _CAPTURE
    finally:
        _CAPTURE = prev


def record_activation(p: Dict[str, Any], x: jax.Array) -> None:
    """Called by LutLinear on every apply; no-op unless capturing."""
    if _CAPTURE is not None and "z" in p and not isinstance(
            x, jax.core.Tracer):
        key = id(p["z"])
        flat = np.asarray(x).reshape(-1, x.shape[-1])
        prev = _CAPTURE.get(key)
        _CAPTURE[key] = flat if prev is None else np.concatenate(
            [prev, flat], axis=0)


def _walk_lut_layers(tree, fn):
    """Apply fn to every sub-dict that looks like a LutLinear (has w & z)."""
    if isinstance(tree, dict):
        if "z" in tree and "w" in tree:
            return fn(tree)
        return {k: _walk_lut_layers(v, fn) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk_lut_layers(v, fn) for v in tree)
    return tree


def kmeans_init_from_capture(params, captured: Dict[int, np.ndarray],
                             qc: QuantConfig, iters: int = 10,
                             seed: int = 0) -> Any:
    """Replace every captured layer's centroids with k-means of its inputs.

    Args:
      params: model params pytree containing LutLinear sub-dicts (w & z).
      captured: ``id(layer["z"]) -> (rows, K)`` activation matrix from
        :func:`capture_activations`.
      qc: quant config; ``qc.spec`` fixes (v, c, metric) for k-means.
      iters: Lloyd iterations per layer; seed: base PRNG seed (offset by
        a per-layer counter so layers get distinct inits).

    Returns: params with each captured layer's ``z`` replaced by its
    per-subspace k-means codebook (dtype preserved); uncaptured layers
    are returned untouched.
    """
    counter = [0]

    def init(layer):
        key = id(layer["z"])
        if key not in captured:
            return layer
        counter[0] += 1
        acts = jnp.asarray(captured[key])
        k = layer["w"].shape[0]
        z = kmeans_codebook(acts, k, qc.spec, iters=iters,
                            key=jax.random.PRNGKey(seed + counter[0]))
        out = dict(layer)
        out["z"] = z.astype(layer["z"].dtype)
        return out

    return _walk_lut_layers(params, init)


def convert(apply_fn: Callable, params, calib_batch, qc: QuantConfig,
            iters: int = 10, seed: int = 0):
    """LUTBoost stage ①: run one calibration forward, k-means-init centroids.

    Args:
      apply_fn: ``apply_fn(params, batch)`` running the model; it must
        execute every LutLinear *eagerly* (outside jit) so the capture
        hook sees concrete arrays.
      params: params whose LutLinear layers already carry ``z`` leaves
        (init the model with a ``lut_train`` QuantConfig).
      calib_batch: one representative batch — its activations define the
        centroid init.
      qc / iters / seed: forwarded to :func:`kmeans_init_from_capture`.

    Returns: params with calibrated centroids (stage ② trains them).
    """
    with capture_activations() as captured:
        apply_fn(params, calib_batch)
    return kmeans_init_from_capture(params, captured, qc, iters, seed)


# ---------------------------------------------------------------------------
# Stages ②/③: trainable-parameter masking + schedule
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LutBoostSchedule:
    """Paper §VII-A hyper-parameters (ResNet defaults)."""
    stage2_steps: int = 1000
    stage3_steps: int = 5000
    lr_stage2: float = 1e-3
    lr_stage3: float = 5e-4
    recon_weight_stage2: float = 0.05
    recon_weight_stage3: float = 0.05

    def stage(self, step: int) -> int:
        return 2 if step < self.stage2_steps else 3

    def lr(self, step: int) -> float:
        return self.lr_stage2 if step < self.stage2_steps else self.lr_stage3

    def recon_weight(self, step: int) -> float:
        return (self.recon_weight_stage2 if step < self.stage2_steps
                else self.recon_weight_stage3)

    @property
    def total_steps(self) -> int:
        return self.stage2_steps + self.stage3_steps


def centroid_only_mask(params) -> Any:
    """Pytree of bools matching ``params``: True only on centroid (``z``)
    leaves — the stage-② trainable set (weights frozen)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def is_centroid(path) -> bool:
        last = path[-1]
        return getattr(last, "key", None) == "z"

    paths = {jax.tree_util.keystr(p) for p, _ in flat if is_centroid(p)}
    return jax.tree_util.tree_map_with_path(
        lambda p, _: jax.tree_util.keystr(p) in paths, params)


def stage_mask(params, stage: int):
    """Trainable mask for a LUTBoost stage: centroids-only for stage ②,
    everything for stage ③."""
    if stage == 2:
        return centroid_only_mask(params)
    return jax.tree_util.tree_map(lambda _: True, params)


def apply_mask(grads, mask):
    """Zero out gradient leaves wherever ``mask`` is False (frozen)."""
    return jax.tree_util.tree_map(
        lambda g, m: g if m else jnp.zeros_like(g), grads, mask)


# ---------------------------------------------------------------------------
# Deployment: precompute every LUT
# ---------------------------------------------------------------------------

def strip_model(params):
    """Drop every dense weight whose layer has a precomputed LUT
    (:func:`~repro.core.lut.strip_for_inference` over the whole tree)."""
    return _walk_lut_layers(params, strip_for_inference)


def precompute_model(params, qc: QuantConfig):
    """Build inference LUTs for every LutLinear in the tree (paper step-2).

    Adds ``lut (nc, c, N)`` — int8 plus ``lut_scale (N,)`` when
    ``qc.lut_dtype == "int8"`` — to each LutLinear so it can serve in
    ``mode="lut_infer"`` (no dense GEMMs at runtime; the serving engines
    consume these params directly)."""
    return _walk_lut_layers(params, lambda p: precompute_layer(p, qc))
