"""Plain reference of a Qwen2-style decoder, and its seeded weights.

Written from the architecture's published description, in plain
``jax.numpy`` and float32 at full matmul precision, with no kernel, cache,
paging or batching of the program under test. It imports nothing of the
program.

* :func:`init_params` draws the weights from a seed, in the parameter
  layout that the serving engine takes. The benchmark's set-up hands these
  to the program; the reference draws them again itself, layer by layer
  (:func:`layer_params`), and takes nothing the program made.
* :meth:`Reference.hidden` runs the reference forward pass over whole token
  sequences, one layer at a time, drawing each layer's weights inside the
  layer's program so that only one layer is ever resident.

Projections follow the configuration's ``projection``:

* ``dense``: ``x @ w + b``;
* ``lut_infer`` (the paper's VQ-AMM): the input is cut into sub-vectors of
  ``v``; each is replaced by its nearest of ``c`` centroids (L2); the
  output is the sum, over sub-vectors, of the table row
  ``z[k, j] . w[k*v:(k+1)*v]`` of the picked centroid, the table quantized
  to symmetric int8 per output column (``amax / 127``).

The control variants compute the same thing one precision step lower:
``w8a8`` (weights int8 per output column, activations int8 per row) for a
bf16 configuration, and int4 tables (``amax / 7``) for the int8-table one.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    theta: float
    tie: bool
    qkv_bias: bool
    dtype: str
    mode: str                 # dense | lut_infer
    v: int = 8
    c: int = 16
    table_bits: int = 8

    @property
    def lut(self) -> bool:
        return self.mode == "lut_infer"


def sizes_from_config(cfg: dict) -> Sizes:
    """:class:`Sizes` from a configuration file of ``bench/configs``."""
    p = cfg["projection"]
    heads = cfg["num_attention_heads"]
    return Sizes(
        layers=cfg["num_hidden_layers"], d=cfg["hidden_size"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], theta=cfg["rope_theta"],
        tie=cfg["tie_word_embeddings"], qkv_bias=cfg.get("qkv_bias", False),
        dtype=cfg["torch_dtype"], mode=p["mode"], v=p.get("v", 8),
        c=p.get("c", 16),
        table_bits=8 if p.get("lut_dtype", "int8") == "int8" else 32)


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def _proj(s: Sizes, key, k: int, n: int, bias: bool) -> dict:
    dt = jnp.dtype(s.dtype)
    kw, kz = jax.random.split(key)
    p = {"w": (k ** -0.5 * jax.random.normal(kw, (k, n))).astype(dt)}
    if bias:
        p["b"] = jnp.zeros((n,), dt)
    if s.lut:
        p["z"] = 0.02 * jax.random.normal(
            kz, (k // s.v, s.c, s.v), dtype=dt)
    return p


def layer_params(s: Sizes, key) -> dict:
    """One decoder layer's weights from its layer key."""
    dt = jnp.dtype(s.dtype)
    ka, kf = jax.random.split(key)
    ks = jax.random.split(ka, 4)
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    attn = {"wq": _proj(s, ks[0], s.d, q, s.qkv_bias),
            "wk": _proj(s, ks[1], s.d, kv, s.qkv_bias),
            "wv": _proj(s, ks[2], s.d, kv, s.qkv_bias),
            "wo": _proj(s, ks[3], q, s.d, False),
            "norm": jnp.zeros((s.d,), dt)}
    km = jax.random.split(kf, 3)
    mlp = {"wg": _proj(s, km[0], s.d, s.ff, False),
           "wu": _proj(s, km[1], s.d, s.ff, False),
           "wd": _proj(s, km[2], s.ff, s.d, False),
           "norm": jnp.zeros((s.d,), dt)}
    return {"attn": attn, "mlp": mlp}


def _keys(s: Sizes, key):
    ke, kb, kh, _ = jax.random.split(key, 4)
    return ke, jax.random.split(kb, s.layers), kh


def _embed(s: Sizes, ke):
    return (0.02 * jax.random.normal(ke, (s.vocab, s.d))).astype(s.dtype)


def _head(s: Sizes, kh):
    return (0.02 * jax.random.normal(kh, (s.d, s.vocab))).astype(s.dtype)


def seed_key(seed: int):
    """PRNG key of a seed of any size up to 62 bits (the low 31 bits seed
    the key, the rest are folded in)."""
    seed = int(seed) % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_params(s: Sizes, key) -> dict:
    """All weights, stacked over layers, in the serving engine's layout
    (``embed``, ``blocks/{attn,mlp}/w*/{w,b,z}``, norms, ``head``).
    Jit it: each stacked weight is drawn and cast in one program."""
    ke, lk, kh = _keys(s, key)
    p = {"final_norm": jnp.zeros((s.d,), s.dtype), "embed": _embed(s, ke),
         "blocks": jax.vmap(lambda k: layer_params(s, k))(lk)}
    if not s.tie:
        p["head"] = _head(s, kh)
    return p


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _rms(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))


def _rope(x, theta):
    """Rotate-half RoPE. x (B, T, H, D) at positions 0..T-1."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quant_cols(a, levels: int):
    """Symmetric per-column (last axis) quantization to +-levels: the
    integer values (as float32) and the (N,) scale."""
    amax = jnp.max(jnp.abs(a), axis=tuple(range(a.ndim - 1)))
    scale = jnp.maximum(amax, 1e-8) / levels
    return jnp.clip(jnp.round(a / scale), -levels, levels), scale


def _dense(x, p, variant: str):
    w = p["w"].astype(jnp.float32)
    if variant == "control":                    # w8a8
        wq, sw = _quant_cols(w, 127)
        w = wq * sw
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        sx = jnp.maximum(amax, 1e-8) / 127
        x = jnp.clip(jnp.round(x / sx), -127, 127) * sx
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _lut(s: Sizes, x, p, variant: str):
    z = p["z"].astype(jnp.float32)                       # (nc, c, v)
    nc = z.shape[0]
    w = p["w"].astype(jnp.float32).reshape(nc, s.v, -1)
    table = jnp.einsum("kcv,kvn->kcn", z, w, precision=HI)
    bits = 4 if variant == "control" else s.table_bits
    xs = x.reshape(*x.shape[:-1], nc, s.v)
    # squared L2 distance to every centroid, expanded (as the paper's CCM
    # computes it) so that no (.., nc, c, v) difference tensor is built
    dist = (jnp.sum(xs * xs, -1)[..., None]
            - 2.0 * jnp.einsum("...kv,kcv->...kc", xs, z, precision=HI)
            + jnp.sum(z * z, -1))
    onehot = jax.nn.one_hot(jnp.argmin(dist, axis=-1), s.c,
                            dtype=jnp.float32)
    if bits >= 32:
        return jnp.einsum("...kc,kcn->...n", onehot, table, precision=HI)
    # integer table entries and 0/1 one-hots are exact in one bf16 pass,
    # and their sums exact in the float32 accumulator
    tq, scale = _quant_cols(table, (1 << (bits - 1)) - 1)
    return jnp.einsum("...kc,kcn->...n", onehot, tq,
                      preferred_element_type=jnp.float32) * scale


def _project(s: Sizes, x, p, variant: str):
    st = _store(s)
    out = st(_lut(s, x, p, variant) if s.lut else _dense(x, p, variant))
    if "b" in p:
        out = st(out + p["b"].astype(jnp.float32))
    return out


def _store(s: Sizes):
    """Rounding to the configuration's activation dtype: every op is
    computed in float32, and what a layer passes on (norm outputs,
    projection outputs, attention probabilities and outputs, residual
    sums) is held in that dtype, as the configuration states."""
    dt = jnp.dtype(s.dtype)
    return lambda a: a.astype(dt).astype(jnp.float32)


def _layer(s: Sizes, x, key, variant: str):
    """One decoder layer over (B, T, d), causal from position 0."""
    st = _store(s)
    p = layer_params(s, key)
    b, t, _ = x.shape
    a = p["attn"]
    xn = st(_rms(x, a["norm"], s.eps))
    q = _project(s, xn, a["wq"], variant).reshape(b, t, s.heads, s.head_dim)
    k = _project(s, xn, a["wk"], variant).reshape(b, t, s.kv_heads,
                                                  s.head_dim)
    v = _project(s, xn, a["wv"], variant).reshape(b, t, s.kv_heads,
                                                  s.head_dim)
    q, k = st(_rope(q, s.theta)), st(_rope(k, s.theta))
    g = s.heads // s.kv_heads
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * s.head_dim ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    o = st(jnp.einsum("bhqk,bkhd->bqhd", st(jax.nn.softmax(sc, axis=-1)), v,
                      precision=HI)).reshape(b, t, -1)
    x = st(x + _project(s, o, a["wo"], variant))
    m = p["mlp"]
    xn = st(_rms(x, m["norm"], s.eps))
    h = st(jax.nn.silu(_project(s, xn, m["wg"], variant))
           * _project(s, xn, m["wu"], variant))
    return st(x + _project(s, h, m["wd"], variant))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(s: Sizes, x, key, variant: str):
    return _layer(s, x, key, variant)


@functools.partial(jax.jit, static_argnums=0)
def _embed_rows(s: Sizes, ke, tokens):
    return _embed(s, ke)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=0)
def _final_norm(s: Sizes, x):
    return _store(s)(_rms(x, jnp.zeros((s.d,), jnp.float32), s.eps))


@functools.partial(jax.jit, static_argnums=0)
def _head_of(s: Sizes, ke, kh):
    return _embed(s, ke).T if s.tie else _head(s, kh)


@dataclasses.dataclass
class Reference:
    """The reference over a batch of sequences, for one weight seed."""
    s: Sizes
    seed: int

    def __post_init__(self):
        self._ke, self._lk, self._kh = _keys(self.s, seed_key(self.seed))

    def hidden(self, tokens: np.ndarray, variant: str = "ref"):
        """(B, T) int32 tokens -> (B, T, d) final-normed states."""
        x = _embed_rows(self.s, self._ke, jnp.asarray(tokens))
        for li in range(self.s.layers):
            x = _layer_jit(self.s, x, self._lk[li], variant)
        return _final_norm(self.s, x)

    def head(self) -> jax.Array:
        """(d, vocab) output head, in the served dtype."""
        return _head_of(self.s, self._ke, self._kh)


ROW_BLOCK = 256


@jax.jit
def _gap_block(rows, ctl_rows, head, served):
    """For a block of positions: the served token's gap below the
    reference's best, and the gap of the token the control puts first."""
    h = head.astype(jnp.float32)
    lg = jnp.einsum("rd,dv->rv", rows, h, precision=HI)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, served[:, None], axis=-1)[:, 0]
    pick = jnp.argmax(jnp.einsum("rd,dv->rv", ctl_rows, h, precision=HI), -1)
    ctl = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
    return best - got, best - ctl


def served_gaps(ref: Reference, seqs: List[List[int]], first: List[int],
                length: int, batch: int, control: bool = False):
    """Gaps of served tokens below the reference's best.

    ``seqs[i]`` is a prompt followed by served tokens, of which those from
    index ``first[i]`` on were served. The reference runs over every
    sequence (padded to ``length``, ``batch`` at a time) and is read at
    each position that predicts a served token. Returns one gap per served
    token, and with ``control`` also the gaps of the tokens that the
    control (the reference one precision step lower) puts first at the
    same positions (else None)."""
    head = ref.head()
    served, ctl = [], []
    for lo in range(0, len(seqs), batch):
        chunk = seqs[lo:lo + batch]
        toks = np.zeros((batch, length), np.int32)
        for i, sq in enumerate(chunk):
            toks[i, :len(sq)] = sq
        h = ref.hidden(toks, "ref")
        hc = ref.hidden(toks, "control") if control else h
        bi, pos, tok = [], [], []
        for i, sq in enumerate(chunk):
            f = first[lo + i]
            bi += [i] * (len(sq) - f)
            pos += list(range(f - 1, len(sq) - 1))
            tok += list(sq[f:])
        for r in range(0, len(pos), ROW_BLOCK):
            n = len(pos[r:r + ROW_BLOCK])
            idx = (np.resize(bi[r:r + n], ROW_BLOCK),
                   np.resize(pos[r:r + n], ROW_BLOCK))
            t = np.resize(np.asarray(tok[r:r + n], np.int32), ROW_BLOCK)
            g, gc = _gap_block(h[idx], hc[idx], head, jnp.asarray(t))
            served.append(np.asarray(g, np.float64)[:n])
            ctl.append(np.asarray(gc, np.float64)[:n])
        del h, hc
    cat = lambda a: np.concatenate(a) if a else np.zeros(0)
    return cat(served), (cat(ctl) if control else None)
