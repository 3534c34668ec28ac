"""Jaxpr/lowering invariants for the serving + kernel entry points.

Traces every public entry point on a tiny smoke-config fixture and
asserts *structural* properties of the jaxpr — things eyeballing HLO
can't police between PRs:

  * **no f64** anywhere (CPU silently promotes; TPU would either crash
    or run at 1/8th throughput — either way the perf claims die);
  * **no transfer ops** (``device_put``) inside a traced entry point —
    a host round-trip inside the step function serializes the pipeline;
  * **gather budgets**: the flash ``"ref"`` path's documented claim is
    that the only pool-sized gather is the *score* gather (scores are
    ``KVH*G`` floats per key vs ``KVH*D`` for a K row — the HBM-traffic
    win of PR 7). The budget makes "no-gather" a checked property: a
    regression that reintroduces a dense KV-view gather fails the run.
    Budgets are exact eqn counts on the pinned fixture and are
    layer-count independent (measured: the flash paths score all layers
    in one batched call);
  * **donation**: the serving jits donate the KV pool
    (``donate_argnums=(2,)``); the check lowers each jit and counts
    ``tf.aliasing_output`` annotations — a signature change that makes
    XLA silently ignore donation doubles pool HBM.

Budgets (empirical on the qwen smoke fixture, asserted exact-or-under):

  ================================  =======  =============================
  entry point                       gathers  what they are
  ================================  =======  =============================
  flash_decode/pallas                  1     self-term row fold only
  flash_decode/ref                     2     score gather + self-term fold
  decode_paged/pallas                  3     embed + self-term + write tgt
  decode_paged/ref                     4     + score gather
  decode_paged/gather                  5     legacy dense-view baseline
  prefill_paged                        4     embed + view(k,v) + slice
  verify_paged                         4     embed + view(k,v) + rows
  vq_amm (ref & fused)                 0     LUT path is gather-free
  ================================  =======  =============================

Quantized-pool (``kv_quant="vq"``) variants — the pool gathers move
uint8 CODES (``nc`` bytes/token/head), never fp rows; the ref/pallas
flash impls replace the centroid lookup with one-hot contractions, so
only the legacy gather path pays decode gathers (the tiny ``z`` tables):

  ================================  =======  =============================
  flash_decode/kvq-pallas              1     self-term fold (codes DMAed)
  flash_decode/kvq-ref                 3     2 code gathers + self-term
  decode_paged/kvq-pallas              3     same as fp — codes add none
  decode_paged/kvq-ref                 5     + 2 code gathers, - score
  decode_paged/kvq-gather              7     code gathers + z decodes
  prefill_paged/kvq                    6     view decodes via z gathers
  verify_paged/kvq                     6     view decodes via z gathers
  ================================  =======  =============================

KVQ donation expects >= 6 aliases: k + v pools AND the four codebook
leaves must all pass through the serving jits in place.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .findings import Finding

from jax.extend.core import ClosedJaxpr, Jaxpr

#: primitives that move data between host and device inside a trace
TRANSFER_PRIMS = ("device_put",)

_ALIAS_RE = re.compile(r"tf\.aliasing_output")


def iter_eqns(jaxpr, path: str = ""):
    """Yield ``(eqn, path)`` over a jaxpr and every sub-jaxpr (pjit,
    scan, cond, pallas_call, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for pname, v in eqn.params.items():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for vv in vs:
                sub = None
                if isinstance(vv, ClosedJaxpr):
                    sub = vv.jaxpr
                elif isinstance(vv, Jaxpr):
                    sub = vv
                if sub is not None:
                    yield from iter_eqns(
                        sub, f"{path}/{eqn.primitive.name}")


def _src_of(eqn) -> Tuple[str, int]:
    """Best-effort repo source location of one eqn."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return "", 0
    for fr in tb.frames:
        fname = fr.file_name or ""
        if "repro" in fname and "analysis" not in fname:
            idx = fname.rfind("src/")
            return fname[idx:] if idx >= 0 else fname, fr.line_num
    return "", 0


def check_invariants(closed: "ClosedJaxpr", *, name: str,
                     forbid_f64: bool = True,
                     forbid_transfer: bool = True,
                     gather_budget: Optional[int] = None) -> List[Finding]:
    """Structural checks over one traced entry point's closed jaxpr."""
    import jax.numpy as jnp
    out: List[Finding] = []
    gathers = 0
    f64_seen: Dict[str, Tuple[str, int]] = {}

    def scan_aval(v, where):
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None and dt == jnp.float64:
            f64_seen.setdefault(where, where_src)

    where_src = ("", 0)
    for cv, c in zip(closed.jaxpr.constvars, closed.consts):
        dt = getattr(c, "dtype", None)
        if forbid_f64 and dt is not None and dt == jnp.float64:
            f64_seen.setdefault("const", ("", 0))
    for eqn, path in iter_eqns(closed.jaxpr):
        pname = eqn.primitive.name
        where_src = _src_of(eqn)
        if pname == "gather":
            gathers += 1
        if forbid_transfer and pname in TRANSFER_PRIMS:
            src, ln = where_src
            out.append(Finding(
                "jaxpr-transfer", src, ln, name,
                f"{pname}@{path or '/'}#{len(out)}",
                f"{name}: transfer op `{pname}` inside the traced entry "
                f"point (host round-trip in the compiled step)", "error",
                "move the transfer outside the jit boundary"))
        if forbid_f64:
            for v in list(eqn.outvars) + list(eqn.invars):
                scan_aval(v, f"{pname}@{path or '/'}")
    for where, (src, ln) in sorted(f64_seen.items()):
        out.append(Finding(
            "jaxpr-f64", src, ln, name, f"f64@{where}",
            f"{name}: float64 value at {where} — silent f64 promotion "
            f"(TPU-hostile, doubles HBM traffic)", "error",
            "cast to float32 / check jnp dtype promotion at this site"))
    if gather_budget is not None and gathers > gather_budget:
        out.append(Finding(
            "jaxpr-gather-budget", "", 0, name, "gather-budget",
            f"{name}: {gathers} gather ops > documented budget "
            f"{gather_budget} — a dense KV-view gather (or similar) "
            f"crept back into the hot path", "error",
            "keep pool reads score-sized (docs/kernels.md §Paged flash "
            "decode); raise the budget only with a traffic argument"))
    return out


def check_donation(jitted, args, *, name: str, min_aliases: int,
                   ) -> List[Finding]:
    """Lower a jit with donated args and assert the aliases survived."""
    txt = jitted.lower(*args).as_text()
    n = len(_ALIAS_RE.findall(txt))
    if n >= min_aliases:
        return []
    return [Finding(
        "jaxpr-donation", "", 0, name, "donation",
        f"{name}: only {n} donated-buffer aliases in the lowered module "
        f"(expected >= {min_aliases}) — the KV pool is being copied "
        f"instead of updated in place", "error",
        "check donate_argnums still points at the kv pytree and that "
        "output shapes/dtypes match the donated buffers")]


# ---------------------------------------------------------------------------
# entry-point registry (tiny smoke fixtures)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EntryCheck:
    """One registered entry point: a fixture builder plus its budgets."""
    name: str
    build: Callable[[], Tuple[Callable, tuple]]   # -> (fn, args)
    gather_budget: Optional[int] = None
    donate_argnums: Tuple[int, ...] = ()
    min_aliases: int = 0


_FIXTURE_CACHE: dict = {}


def _serve_fixture():
    """Tiny qwen smoke model + paged state, built once per process."""
    if "serve" in _FIXTURE_CACHE:
        return _FIXTURE_CACHE["serve"]
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.core.lut import DENSE
    from repro.models.model import Model
    from repro.serve import PageTable

    cfg = get_smoke_config("qwen1.5-4b").replace(attn_impl="naive")
    m = Model(cfg)
    params = m.init(jax.random.PRNGKey(0), DENSE)
    slots, max_seq, ps = 2, 32, 8
    pt = PageTable(num_slots=slots, max_seq=max_seq, page_size=ps)
    kv = m.init_paged_cache(slots, max_seq, ps, pt.allocator.num_pages)
    for s in range(slots):
        pt.ensure(s, 20)
    fix = {
        "model": m, "params": params, "kv": kv, "table": pt.device(),
        "tok": jnp.zeros((slots, 1), jnp.int32),
        "pos": jnp.asarray([5, 9], jnp.int32),
        "ptoks": jnp.zeros((1, 8), jnp.int32),
        "vtoks": jnp.zeros((slots, 3), jnp.int32),
        "nlive": jnp.asarray([3, 3], jnp.int32),
        "DENSE": DENSE,
    }
    _FIXTURE_CACHE["serve"] = fix
    return fix


def _kvq_fixture():
    """The serve fixture re-based on a vector-quantized page pool."""
    if "kvq" in _FIXTURE_CACHE:
        return _FIXTURE_CACHE["kvq"]
    import jax
    from repro.core.kv_codebook import KVCodebook
    from repro.serve import PageTable
    fx = dict(_serve_fixture())
    m = fx["model"]
    cfg = m.cfg
    key = jax.random.PRNGKey(1)
    rows = jax.random.normal(
        key, (cfg.num_layers, 24, cfg.num_kv_heads, cfg.head_dim))
    cb = KVCodebook.fit(rows, rows + 0.1, v=4, c=16, iters=2)
    slots, max_seq, ps = 2, 32, 8
    pt = PageTable(num_slots=slots, max_seq=max_seq, page_size=ps)
    for s in range(slots):
        pt.ensure(s, 20)
    fx["kv"] = m.init_paged_cache(slots, max_seq, ps,
                                  pt.allocator.num_pages, codebook=cb)
    fx["table"] = pt.device()
    _FIXTURE_CACHE["kvq"] = fx
    return fx


def _decode_entry(flash: str, kvq: bool = False):
    def build():
        fx = _kvq_fixture() if kvq else _serve_fixture()
        m, qc = fx["model"], fx["DENSE"].replace(
            flash=flash, kv_quant="vq" if kvq else "none")

        def fn(p, t, kv, pt, po):
            return m.decode_paged(p, t, kv, pt, po, qc)
        return fn, (fx["params"], fx["tok"], fx["kv"], fx["table"],
                    fx["pos"])
    return build


def _prefill_entry(kvq: bool = False):
    def build():
        import jax.numpy as jnp
        fx = _kvq_fixture() if kvq else _serve_fixture()
        m = fx["model"]
        qc = fx["DENSE"].replace(kv_quant="vq" if kvq else "none")

        def fn(p, t, kv, pt, s, po, v):
            return m.prefill_paged(p, t, kv, pt, s, po, v, qc)
        return fn, (fx["params"], fx["ptoks"], fx["kv"], fx["table"],
                    jnp.int32(0), jnp.int32(0), jnp.int32(8))
    return build


def _verify_entry(kvq: bool = False):
    def build():
        fx = _kvq_fixture() if kvq else _serve_fixture()
        m = fx["model"]
        qc = fx["DENSE"].replace(kv_quant="vq" if kvq else "none")

        def fn(p, t, kv, pt, po, nl):
            return m.verify_paged(p, t, kv, pt, po, nl, qc)
        return fn, (fx["params"], fx["vtoks"], fx["kv"], fx["table"],
                    fx["pos"], fx["nlive"])
    return build


def _flash_entry(impl: str, kvq: bool = False):
    def build():
        import jax.numpy as jnp
        from repro.kernels.flash_decode import flash_decode_paged
        b, kvh, g, d, np_, ps = 2, 2, 2, 16, 4, 8
        nc = 4
        q = jnp.ones((b, 1, kvh * g, d))
        if kvq:              # stacked two-layer head-major pool + codebook
            kp = jnp.ones((2, np_ + 1, kvh, ps, nc), jnp.uint8)
            cb = {"zk": jnp.ones((2, nc, 16, 4)),
                  "zv": jnp.ones((2, nc, 16, 4)),
                  "sk": jnp.ones((2, kvh)), "sv": jnp.ones((2, kvh))}
        else:
            kp = jnp.ones((2, np_ + 1, kvh, ps, d))
            cb = None
        kn = jnp.ones((b, 1, kvh, d))
        phys = jnp.zeros((b, np_), jnp.int32)
        pos = jnp.asarray([5, 7], jnp.int32)

        def fn(q, kp, vp, kn, vn, phys, pos):
            return flash_decode_paged(q, kp, vp, kn, vn, phys, pos,
                                      layer=1, impl=impl, codebook=cb,
                                      interpret=True)
        return fn, (q, kp, kp, kn, kn, phys, pos)
    return build


def _vq_amm_entry(impl: str):
    def build():
        import jax.numpy as jnp
        from repro.kernels import ops
        x = jnp.ones((4, 8, 4))
        z = jnp.ones((8, 16, 4))
        lut = jnp.ones((8, 16, 32))

        def fn(x, z, lut):
            return ops.vq_amm(x, z, lut, impl=impl)
        return fn, (x, z, lut)
    return build


def registry() -> List[EntryCheck]:
    """All registered entry points (budgets documented in the module
    docstring; donation expectations = KV-pool leaves k + v)."""
    return [
        EntryCheck("decode_paged/gather", _decode_entry("gather"),
                   gather_budget=5, donate_argnums=(2,), min_aliases=2),
        EntryCheck("decode_paged/ref", _decode_entry("ref"),
                   gather_budget=4, donate_argnums=(2,), min_aliases=2),
        EntryCheck("decode_paged/pallas", _decode_entry("pallas"),
                   gather_budget=3, donate_argnums=(2,), min_aliases=2),
        EntryCheck("prefill_paged", _prefill_entry(), gather_budget=4,
                   donate_argnums=(2,), min_aliases=2),
        EntryCheck("verify_paged", _verify_entry(), gather_budget=4,
                   donate_argnums=(2,), min_aliases=2),
        EntryCheck("flash_decode/ref", _flash_entry("ref"),
                   gather_budget=2),
        EntryCheck("flash_decode/pallas", _flash_entry("pallas"),
                   gather_budget=1),
        EntryCheck("vq_amm/ref", _vq_amm_entry("ref"), gather_budget=0),
        EntryCheck("vq_amm/fused", _vq_amm_entry("fused"),
                   gather_budget=0),
        # quantized-pool variants (budgets in the module docstring): the
        # pools donate through unchanged, plus the 4 codebook leaves
        EntryCheck("decode_paged/kvq-gather",
                   _decode_entry("gather", kvq=True),
                   gather_budget=7, donate_argnums=(2,), min_aliases=6),
        EntryCheck("decode_paged/kvq-ref", _decode_entry("ref", kvq=True),
                   gather_budget=5, donate_argnums=(2,), min_aliases=6),
        EntryCheck("decode_paged/kvq-pallas",
                   _decode_entry("pallas", kvq=True),
                   gather_budget=3, donate_argnums=(2,), min_aliases=6),
        EntryCheck("prefill_paged/kvq", _prefill_entry(kvq=True),
                   gather_budget=6, donate_argnums=(2,), min_aliases=6),
        EntryCheck("verify_paged/kvq", _verify_entry(kvq=True),
                   gather_budget=6, donate_argnums=(2,), min_aliases=6),
        EntryCheck("flash_decode/kvq-ref", _flash_entry("ref", kvq=True),
                   gather_budget=3),
        EntryCheck("flash_decode/kvq-pallas",
                   _flash_entry("pallas", kvq=True), gather_budget=1),
    ]


def check_entry(ec: EntryCheck) -> List[Finding]:
    """Trace one registered entry point and run every invariant."""
    import jax
    fn, args = ec.build()
    closed = jax.make_jaxpr(fn)(*args)
    out = check_invariants(closed, name=ec.name,
                           gather_budget=ec.gather_budget)
    if ec.donate_argnums:
        jitted = jax.jit(fn, donate_argnums=ec.donate_argnums)
        out += check_donation(jitted, args, name=ec.name,
                              min_aliases=ec.min_aliases)
    return out


def run_jaxpr_checks(names: Optional[Sequence[str]] = None,
                     ) -> List[Finding]:
    """Run every registered entry check (or the named subset)."""
    out: List[Finding] = []
    for ec in registry():
        if names is not None and ec.name not in names:
            continue
        out.extend(check_entry(ec))
    return out
