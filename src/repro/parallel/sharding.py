"""Sharding rules: params / batches / caches → PartitionSpec trees.

Strategy (megatron-style TP on the ``model`` axis, DP over ``pod``דdata``):

* embeddings shard on the vocab dim; attention q/k/v shard heads
  (column-parallel), the output projection is row-parallel; MLP up/gate are
  column-parallel, down is row-parallel. MoE experts shard the *expert* dim
  (EP). Mamba's fused in_proj is column-parallel, out_proj row-parallel.
* LUT-DLA artefacts: codebooks ``z`` are tiny and follow the *input* (K)
  dim of their projection — replicated for column-parallel projections,
  subspace-sharded for row-parallel ones (assignment is then local to the
  shard, and the LUT accumulate produces partial sums that reduce exactly
  like a dense row-parallel matmul). Precomputed LUTs ``(nc, c, N)`` shard
  like the weight they replace: N for column-parallel, nc for row-parallel.
* KV caches: batch over the data axes when batch ≥ their product,
  otherwise the *sequence* dim is sharded over ``data`` (SP long-context
  decode; GSPMD inserts the distributed-softmax collectives).

Everything is path-rule based so it applies uniformly to stacked scan
params (leading layer dim) and per-expert weights.
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def _spec_for_leaf(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
                   model_axis: str, msize: int) -> P:
    """Per-leaf PartitionSpec. `path` is the keystr, `shape` the leaf shape.

    Any dim assigned to the model axis must divide its size; otherwise that
    dim falls back to replicated (e.g. mamba2's vocab 50280 on a 16-way
    axis)."""
    m = model_axis
    ndim = len(shape)

    def lead(base: Tuple, want_ndim: int) -> P:
        """Left-pad `base` with None (stacked layer / expert dims) and drop
        the model axis from any non-divisible dim."""
        pad = want_ndim - len(base)
        axes = [None] * pad + list(base)
        axes = [a if (a is None or shape[i] % msize == 0) else None
                for i, a in enumerate(axes)]
        return P(*axes)

    # ---- embeddings & heads -------------------------------------------
    if "embed" in path and ndim == 2:
        return lead((m, None), ndim)            # vocab-sharded
    if "heads" in path and ndim == 3:           # audio heads (Q, D, V)
        return lead((None, None, m), ndim)
    if "head" in path and ndim == 2:
        return lead((None, m), ndim)
    if "in_proj']" in path and "blocks" not in path and ndim == 2:
        return P()                              # audio stub input proj (tiny)

    # ---- MoE ----------------------------------------------------------
    if "router" in path:
        return lead((None, None), ndim)         # replicated (tiny, hot)
    if "shared_w" in path:
        # shared experts are few (can't shard E over the model axis):
        # tensor-parallel instead — up/gate column-parallel, down row-parallel.
        rowwise = "shared_wd" in path
        if path.endswith("['w']"):              # (..., SE, K, N)
            return lead((None, m, None) if rowwise else (None, None, m), ndim)
        if path.endswith("['z']"):
            return lead((None, m, None, None) if rowwise
                        else (None, None, None, None), ndim)
        if path.endswith("['lut']"):            # (..., SE, nc, c, N)
            return lead((None, m, None, None) if rowwise
                        else (None, None, None, m), ndim)
        if path.endswith("['lut_scale']"):
            return lead((None, None) if rowwise else (None, m), ndim)
    for key in ("wg", "wu", "wd"):
        if f"['{key}']" in path and "moe" in path:
            if path.endswith("['w']"):          # (..., E, K, N)
                return lead((m, None, None), ndim)
            if path.endswith("['z']"):          # (..., E, nc, c, v)
                return lead((m, None, None, None), ndim)
            if path.endswith("['lut']"):        # (..., E, nc, c, N)
                return lead((m, None, None, None), ndim)
            if path.endswith("['lut_scale']"):
                return lead((m, None), ndim)

    # ---- column-parallel projections (shard output dim N) -------------
    col = ("['wq']", "['wk']", "['wv']", "['wg']", "['wu']", "['in_proj']")
    # ---- row-parallel projections (shard input dim K = nc·v) ----------
    row = ("['wo']", "['wd']", "['out_proj']")

    if any(k in path for k in col):
        if path.endswith("['w']"):
            return lead((None, m), ndim)
        if path.endswith("['b']"):
            return lead((m,), ndim)
        if path.endswith("['z']"):
            return lead((None, None, None), ndim)          # replicate
        if path.endswith("['lut']"):
            return lead((None, None, m), ndim)             # N-sharded
        if path.endswith("['lut_scale']"):
            return lead((m,), ndim)
    if any(k in path for k in row):
        if path.endswith("['w']"):
            return lead((m, None), ndim)
        if path.endswith("['b']"):
            return lead((None,), ndim)
        if path.endswith("['z']"):
            return lead((m, None, None), ndim)             # subspace-sharded
        if path.endswith("['lut']"):
            return lead((m, None, None), ndim)             # nc-sharded
        if path.endswith("['lut_scale']"):
            return lead((None,), ndim)

    # ---- mamba channelwise params --------------------------------------
    if "conv_w" in path:
        return lead((None, m), ndim)           # (K, C): channels sharded
    if "conv_b" in path or "gate_norm" in path:
        return lead((m,), ndim)
    if any(k in path for k in ("dt_bias", "A_log", "['D']")):
        return lead((m,), ndim)                # per-head

    # ---- norms & leftovers: replicated ---------------------------------
    return P(*([None] * ndim))


def param_pspecs(params, cfg: ModelConfig, model_axis: str = "model",
                 model_axis_size: int = 16):
    """PartitionSpec tree matching `params`."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for_leaf(_path_str(path),
                                          tuple(getattr(leaf, "shape", ())),
                                          cfg, model_axis, model_axis_size),
        params)


def batch_pspecs(cfg: ModelConfig, data_axes: Tuple[str, ...] = ("data",)):
    """PartitionSpecs for a training batch (batch dim over all DP axes)."""
    da = data_axes if len(data_axes) > 1 else data_axes[0]
    if cfg.family == "audio":
        return {"embeds": P(da, None, None), "labels": P(da, None, None)}
    if cfg.family == "vlm":
        return {"patch_embeds": P(da, None, None), "tokens": P(da, None)}
    return {"tokens": P(da, None)}


def cache_pspecs(cfg: ModelConfig, batch_size: int, mesh: Mesh,
                 data_axes: Tuple[str, ...] = ("data",),
                 model_axis: str = "model"):
    """PartitionSpecs for a decode cache (see Model.init_cache layout).

    If the batch covers the data axes, shard batch; otherwise shard the
    sequence dim over `data` (SP — long-context decode with batch=1).
    """
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    batch_first = batch_size % dp == 0 and batch_size >= dp
    da = data_axes if len(data_axes) > 1 else data_axes[0]
    m = model_axis
    msize = mesh.shape[m]
    mh, md = _kv_model_axes(cfg, m, msize)

    if batch_first:
        kv = P(None, da, None, mh, md)          # (L, B, T, KVH, D)
    else:
        # SP: sequence over data (long-context, batch=1); GSPMD inserts the
        # distributed-softmax collectives for attention over the shards.
        kv = P(None, None,
               da if len(data_axes) == 1 else "data", mh, md)

    pos = P()
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        return {"layers": {"k": kv, "v": kv}, "pos": pos}

    mamba = {
        "conv": P(None, da if batch_first else None, None, m),
        "h": P(None, da if batch_first else None, m, None, None),
    }
    if cfg.family == "ssm":
        return {"layers": mamba, "pos": pos}
    return {"layers": {"mamba": mamba, "attn": {"k": kv, "v": kv}},
            "pos": pos}


def _kv_model_axes(cfg: ModelConfig, model_axis: str, msize: int):
    """Model-axis placement inside a KV cache: prefer kv heads, fall back
    to head_dim (matches the column-parallel wk/wv output sharding), else
    replicate across model."""
    kvh, hd = cfg.num_kv_heads, (cfg.head_dim or 0)
    if kvh and kvh % msize == 0:
        return model_axis, None
    if hd and hd % msize == 0:
        return None, model_axis
    return None, None


def paged_cache_pspecs(cfg: ModelConfig, mesh: Mesh,
                       model_axis: str = "model", quantized: bool = False):
    """PartitionSpecs for the PAGED serving cache (Model.init_paged_cache).

    Layout (see docs/serving.md): attention families store KV as a physical
    page pool ``(L, num_pages+1, KVH, page_size, HD)``. The pool — pages,
    trash page included — is REPLICATED over the data axes: data-parallel
    serving is replica-level (one engine + page table per replica group,
    ``repro.serve.router``), so within one engine only tensor parallelism
    applies, on the kv-head / head-dim axis exactly like the dense cache.
    SSM / hybrid slot-indexed state shards its channel / head dims over
    ``model`` (falling back to replicated on non-divisible dims). The page
    table, positions and tokens are host-managed and replicated.

    ``quantized``: the pool holds uint8 codes ``(L, P+1, KVH, page, nc)``
    plus the codebook pytree. kv heads still shard over ``model`` when
    divisible, but the last dim is the SUBSPACE axis, not head_dim — the
    head_dim fallback does not apply (a centroid spans ``v`` contiguous
    fp lanes that one device must own), so it replicates instead. The
    codebook tables are small and replicated everywhere.
    """
    m = model_axis
    msize = mesh.shape[m]
    mh, md = _kv_model_axes(cfg, m, msize)
    if quantized:
        md = None                           # last dim = subspaces, whole
    kv = P(None, None, mh, None, md)        # (L, P+1, KVH, page, HD|nc)
    if cfg.family in ("dense", "moe", "audio", "vlm"):
        if quantized:
            cbook = {"zk": P(), "zv": P(), "sk": P(), "sv": P()}
            return {"k": kv, "v": kv, "codebook": cbook}
        return {"k": kv, "v": kv}
    conv_dim = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
    mamba = {
        # (L, slots, K-1, C): channels over model, like the dense cache
        "conv": P(None, None, None,
                  m if conv_dim % msize == 0 else None),
        # (L, slots, H, HP, N): ssm heads over model
        "h": P(None, None,
               m if cfg.ssm_nheads % msize == 0 else None, None, None),
    }
    if cfg.family == "ssm":
        return mamba
    # hybrid: slot-dense shared-attn cache (n_inv, slots, T+1, KVH, HD)
    dense = P(None, None, None, mh, md)
    return {"mamba": mamba, "attn": {"k": dense, "v": dense}}


def logical_to_sharding(specs, mesh: Mesh):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda s: isinstance(s, P))
