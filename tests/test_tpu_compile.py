"""Compile the serving kernels for a described TPU v5e, with no chip.

The TPU compiler installed with JAX compiles for a topology that is
described, not attached. It refuses what interpret mode accepts: block
shapes off the (8, 128) tiling, vector ops Mosaic cannot lower, more VMEM
than a kernel may use. Each test compiles one kernel at the Qwen1.5-4B
widths (d_model 2560, d_ff 6912, 20 kv heads of 128) and checks that the
program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.assign import vq_assign_pallas
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.fused_amm import vq_amm_pallas
from repro.kernels.lut_gemm import lut_gemm_pallas

V, C = 8, 16                                  # serving default (v, c)
# (K, N) of every projection: q/k/v/o, MLP gate/up, MLP down
PROJ = [(2560, 2560), (2560, 6912), (6912, 2560)]
ROWS = [8, 32, 256]                           # decode, prefill chunk, mid+
KVH, HD, PAGE, SLOTS, PAGES_PER_SLOT = 20, 128, 16, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # no TPU compiler here fails every test of this file, never skips it
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k", sorted({k for k, _ in PROJ}))
def test_vq_assign_compiles(one_chip, m, k):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda x, z: vq_assign_pallas(x, z),
                   s((m, nc, V), jnp.bfloat16), s((nc, C, V), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", PROJ)
def test_lut_gemm_compiles(one_chip, m, k, n):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda i, t, sc: lut_gemm_pallas(i, t, sc),
                   s((m, nc), jnp.int32), s((nc, C, n), jnp.int8),
                   s((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("m", ROWS)
@pytest.mark.parametrize("k,n", PROJ)
def test_vq_amm_compiles(one_chip, m, k, n):
    s = _spec(one_chip)
    nc = k // V
    hlo = _compile(lambda x, z, t, sc: vq_amm_pallas(x, z, t, sc),
                   s((m, nc, V), jnp.bfloat16), s((nc, C, V), jnp.bfloat16),
                   s((nc, C, n), jnp.int8), s((n,), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("pool", ["fp", "vq"])
def test_flash_decode_compiles(one_chip, pool):
    """The split pass over a stacked two-layer head-major pool, read at a
    traced layer index."""
    s = _spec(one_chip)
    p1 = SLOTS * PAGES_PER_SLOT + 1
    tok = s((SLOTS, 1, KVH, HD), jnp.bfloat16)
    if pool == "vq":                   # kv_v=4, kv_c=16 codes over head_dim
        nc, kc, kv = HD // 4, 16, 4
        pages = s((2, p1, KVH, PAGE, nc), jnp.uint8)
        cb = {"zk": s((2, nc, kc, kv), jnp.float32),
              "zv": s((2, nc, kc, kv), jnp.float32),
              "sk": s((2, KVH), jnp.float32), "sv": s((2, KVH), jnp.float32)}
    else:
        pages = s((2, p1, KVH, PAGE, HD), jnp.bfloat16)
        cb = None

    def step(q, kp, vp, kn, vn, phys, pos, layer, cb):
        return flash_decode_paged(q, kp, vp, kn, vn, phys, pos, layer=layer,
                                  impl="pallas", codebook=cb)

    hlo = _compile(step, tok, pages, pages, tok, tok,
                   s((SLOTS, PAGES_PER_SLOT), jnp.int32),
                   s((SLOTS,), jnp.int32), s((), jnp.int32), cb)
    assert "tpu_custom_call" in hlo


# configurations of the step compiles: Qwen1.5-4B (20 kv heads) and
# Yi-9B (4 kv heads), Yi cut to Qwen's 40 layers so that its bf16 weights
# fit one v5e's 16 GB
STEP_MODELS = {"qwen1.5-4b": {}, "yi-9b": {"num_layers": 40}}


def _step_hlo(one_chip, program, name="qwen1.5-4b"):
    """``program`` ("decode_step" or "prefill_chunk") of the engine at the
    widths of ``name`` (a key of ``STEP_MODELS``; 8 slots of 1024, pages
    of 16, a pool of 512 pages, chunks of 32), compiled with the pool
    donated as the engine does. Returns (HLO text, the pool's HLO
    shape)."""
    from repro.configs import get_config
    from repro.core.lut import DENSE
    from repro.models.model import Model
    from repro.serve.engine import step_programs
    cfg = get_config(name).replace(**STEP_MODELS[name])
    model = Model(cfg)
    qc = DENSE.replace(flash="pallas")
    s = _spec(one_chip)
    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), qc)))
    kv = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(SLOTS, 1024, PAGE, 512)))
    prefill_chunk, decode_step, _ = step_programs(model, qc)
    table = s((SLOTS, PAGES_PER_SLOT), jnp.int32)
    if program == "decode_step":
        fn, args = decode_step, (s((SLOTS, 1), jnp.int32), kv, table,
                                 s((SLOTS,), jnp.int32))
    else:
        scalar = s((), jnp.int32)
        fn, args = prefill_chunk, (s((1, 32), jnp.int32), kv, table,
                                   scalar, scalar, scalar)
    hlo = jax.jit(fn, donate_argnums=(2,)).lower(
        params, *args).compile().as_text()
    assert kv["k"].dtype == jnp.bfloat16
    return hlo, "bf16[" + ",".join(map(str, kv["k"].shape)) + "]"


def _computations(hlo):
    """{computation name: its instruction lines} of an HLO text."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith((" ", "}")):
            comp = line.split(" ", 1)[0]
            comps[comp] = []
        elif comp is not None:
            comps[comp].append(line.strip())
    return comps


def _instructions(comps):
    """{computation: {instruction name: (result dims, opcode, operand
    names, the line)}}: dims is None for a tuple result."""
    import re
    head = re.compile(r"(?:ROOT )?%([\w.-]+) = ")
    dims = re.compile(r"\w+\[([\d,]*)\]")
    out = {}
    for comp, body in comps.items():
        table = out[comp] = {}
        for ins in body:
            m = head.match(ins)
            if not m:
                continue
            rest = ins[m.end():]
            op = re.search(r" ([a-z][\w-]*)\(", rest)
            args, depth = op.end(), 1
            for i in range(op.end(), len(rest)):
                depth += {"(": 1, ")": -1}.get(rest[i], 0)
                if not depth:
                    args = rest[op.end():i]
                    break
            d = None if rest.startswith("(") else dims.match(rest)
            table[m.group(1)] = (
                None if d is None else tuple(map(int, filter(None,
                                                 d.group(1).split(",")))),
                op.group(1), re.findall(r"%([\w.-]+)", args), ins)
    return out


def _source(table, comp, name):
    """Follow ``name`` in ``comp`` back through bitcasts and loop-carried
    tuple elements to the instruction that makes it: (computation, its
    entry in ``table``)."""
    import re
    while True:
        dims, op, args, ins = table[comp][name]
        if op == "bitcast":
            name = args[0]
        elif (op == "get-tuple-element"
              and table[comp][args[0]][1] == "parameter"
              and not comp.startswith("ENTRY")):
            index = int(re.search(r"index=(\d+)", ins).group(1))
            loops = [(c, t) for c, ts in table.items() for t in ts.values()
                     if t[1] == "while" and f"body={comp}" in t[3]]
            assert len(loops) == 1, (comp, len(loops))
            comp, (_, _, (init,), _) = loops[0]
            dims, op, args, ins = table[comp][init]
            assert op == "tuple", ins[:120]
            name = args[index]
        else:
            return comp, table[comp][name]


def test_decode_step_names_its_program_and_flash_kernel(one_chip,
                                                        monkeypatch):
    """The engine's decode step at Qwen1.5-4B's widths (8 slots of 1024,
    pages of 16) compiles as ``jit_decode_step`` and holds one flash
    split call, named ``flash_splits``, in the body of the loop over the
    40 layers; the benchmark's matcher claims that call and no other
    instruction (not the ops that take its results)."""
    import re
    from pathlib import Path
    from repro.configs import get_config
    monkeypatch.syspath_prepend(
        str(Path(__file__).resolve().parents[1] / "bench"))
    from work import flash_decode
    # the kernel branches are chosen by the backend JAX reports
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, _ = _step_hlo(one_chip, "decode_step")
    assert hlo.startswith("HloModule jit_decode_step,")
    comps = _computations(hlo)
    claimed = [(c, ins) for c, body in comps.items() for ins in body
               if ins.startswith(("%", "ROOT %"))
               and flash_decode.matches(ins)]
    assert len(claimed) == 1, [ins[:80] for _, ins in claimed]
    body, ins = claimed[0]
    assert ins.startswith("%flash_splits.")
    assert "flash_decode" not in ins
    loops = [i for b in comps.values() for i in b
             if f"body={body}" in i and " while(" in i]
    assert len(loops) == 1
    cond = re.search(r"condition=(%[\w.-]+)", loops[0]).group(1)
    num_layers = get_config("qwen1.5-4b").num_layers
    assert any(f"constant({num_layers})" in i for i in comps[cond])


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_step_writes_kv_rows_in_place(one_chip, monkeypatch, program):
    """The step writes its fresh K/V rows into the donated pool where it
    lies: no instruction whose result is the whole pool is a copy (a
    relayout into and out of a scatter's layout costs two passes over
    1.68 GB a pool on every step) or a scatter, and the compiled program
    hands both pool buffers back as its outputs."""
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, pool = _step_hlo(one_chip, program)
    assert hlo.startswith(f"HloModule jit_{program},")
    made = re.compile(r"(?:ROOT )?%[\w.-]+ = " + re.escape(pool)
                      + r"\{[^}]*\} ([\w-]+)\(")
    comps = _computations(hlo)
    ops = [m.group(1) for body in comps.values()
           for m in map(made.match, body) if m]
    assert "dynamic-update-slice" in ops
    relayouts = [op for op in ops if op.startswith("copy") or op == "scatter"]
    assert not relayouts, relayouts
    pool_params = {int(m.group(1)) for ins in comps["ENTRY"]
                   for m in [re.search(re.escape(pool)
                                       + r"\{[^}]*\} parameter\((\d+)\)",
                                       ins)] if m}
    assert len(pool_params) == 2
    header = hlo.split("entry_computation_layout=", 1)[0]
    aliased = {int(n) for n in re.findall(r"\{\d+\}: \((\d+), ", header)}
    assert pool_params <= aliased, (pool_params, header)


@pytest.mark.parametrize("name", sorted(STEP_MODELS))
def test_decode_step_reads_pool_layers_in_place(one_chip, monkeypatch,
                                                name):
    """The decode step reads each layer of the stacked head-major K/V pool
    where it lies, for 20 kv heads and for 4: no instruction's result is
    one layer of the pool in any axis order (a per-layer dynamic-slice,
    or its relayout copy), none relays out the whole pool, and the flash
    split call takes the entry's own pool parameters, passed through the
    layer loop and bitcasts only."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, pool = _step_hlo(one_chip, "decode_step", name)
    whole = tuple(map(int, pool[5:-1].split(",")))
    layer = sorted(whole[1:])
    table = _instructions(_computations(hlo))
    for comp, ts in table.items():
        for dims, op, _, ins in ts.values():
            if dims is None:
                continue
            assert sorted(d for d in dims if d != 1) != layer, ins[:120]
            if op in ("copy", "transpose", "scatter"):
                assert sorted(dims) != sorted(whole), ins[:120]
    calls = [(c, t) for c, ts in table.items() for t in ts.values()
             if t[1] == "custom-call" and t[3].startswith("%flash_splits.")]
    assert len(calls) == 1
    comp, (_, _, args, _) = calls[0]
    params = set()
    for arg in args[-2:]:                       # the K and V pools
        where, (dims, op, _, ins) = _source(table, comp, arg)
        assert where.startswith("ENTRY") and op == "parameter", ins[:120]
        assert dims == whole
        params.add(ins)
    assert len(params) == 2


@pytest.mark.parametrize("name", sorted(STEP_MODELS))
def test_prefill_chunk_gathers_view_without_copy(one_chip, monkeypatch,
                                                 name):
    """The prefill chunk gathers the slot's pages of the head-major pool
    into its dense (L, 1, 1024, KVH, HD) view without a second pass over
    it: no copy or transpose has the view's size."""
    import math
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, pool = _step_hlo(one_chip, "prefill_chunk", name)
    l, _, kvh, page, hd = map(int, pool[5:-1].split(","))
    view = l * PAGES_PER_SLOT * page * kvh * hd
    table = _instructions(_computations(hlo))
    relaid = [ins[:120] for ts in table.values()
              for dims, op, _, ins in ts.values()
              if dims is not None and op in ("copy", "transpose")
              and math.prod(dims) == view]
    assert not relaid, relaid


# Moonlight-16B-A3B's widths: 16 heads of latent attention over a latent
# row of 512 + 64 (640 with its tile padding), 8 held experts of 1408 over
# d_model 2048, 128 slots of 1024 positions in pages of 16
ML_SLOTS, ML_ROW, ML_HEADS, ML_RANK = 128, 640, 16, 512


def test_mla_decode_kernel_compiles(one_chip):
    from repro.kernels.mla_decode import mla_decode_paged
    s = _spec(one_chip)
    hlo = _compile(
        lambda q, pool, new, phys, pos, layer: mla_decode_paged(
            q, pool, new, phys, pos, ML_RANK, layer=layer, impl="pallas"),
        s((ML_SLOTS, ML_HEADS, ML_ROW), jnp.float32),
        s((2, ML_SLOTS * PAGES_PER_SLOT + 1, PAGE, ML_ROW), jnp.bfloat16),
        s((ML_SLOTS, ML_ROW), jnp.bfloat16),
        s((ML_SLOTS, PAGES_PER_SLOT), jnp.int32), s((ML_SLOTS,), jnp.int32),
        s((), jnp.int32))
    assert "tpu_custom_call" in hlo and "%mla_decode" in hlo


@pytest.mark.parametrize("rows", [ML_SLOTS, 32])
def test_moe_experts_kernel_compiles(one_chip, rows):
    from repro.kernels.moe_experts import moe_experts_pallas
    s = _spec(one_chip)
    d, f, e = 2048, 1408, 8
    hlo = _compile(moe_experts_pallas, s((rows, d), jnp.bfloat16),
                   s((rows, e), jnp.float32), s((e, d, f), jnp.bfloat16),
                   s((e, d, f), jnp.bfloat16), s((e, f, d), jnp.bfloat16))
    assert "tpu_custom_call" in hlo and "%moe_experts" in hlo


def _latent_step_hlo(one_chip, program):
    """``program`` of the engine for Moonlight-16B-A3B's chip share at its
    published widths, cut to two layers (the dense one and one MoE layer),
    128 slots of 1024 in pages of 16, chunks of 32, the pool donated.
    Returns (HLO text, the latent pool's HLO shape)."""
    from repro.configs import get_config
    from repro.core.lut import DENSE
    from repro.models.model import Model
    from repro.serve.engine import step_programs
    cfg = get_config("moonlight-16b-a3b-ep8").replace(num_layers=2)
    model = Model(cfg)
    qc = DENSE.replace(flash="pallas")
    s = _spec(one_chip)
    on_chip = lambda tree: jax.tree.map(lambda a: s(a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), qc)))
    kv = on_chip(jax.eval_shape(lambda: model.init_paged_cache(
        ML_SLOTS, 1024, PAGE, ML_SLOTS * PAGES_PER_SLOT)))
    prefill_chunk, decode_step, _ = step_programs(model, qc)
    table = s((ML_SLOTS, PAGES_PER_SLOT), jnp.int32)
    if program == "decode_step":
        fn, args = decode_step, (s((ML_SLOTS, 1), jnp.int32), kv, table,
                                 s((ML_SLOTS,), jnp.int32))
    else:
        scalar = s((), jnp.int32)
        fn, args = prefill_chunk, (s((1, 32), jnp.int32), kv, table,
                                   scalar, scalar, scalar)
    hlo = jax.jit(fn, donate_argnums=(2,)).lower(
        params, *args).compile().as_text()
    assert set(kv) == {"latent"} and kv["latent"].dtype == jnp.bfloat16
    return hlo, "bf16[" + ",".join(map(str, kv["latent"].shape)) + "]"


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_latent_step_writes_rows_in_place(one_chip, monkeypatch, program):
    """Moonlight's steps write their latent rows into the donated pool
    where it lies (no whole-pool copy or scatter, the pool handed back as
    an output), and never expand per-head keys or values over a slot's
    positions: no result holds 1024 positions by 16 heads by a head
    width (128 no-RoPE or value, 192 query/key)."""
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, pool = _latent_step_hlo(one_chip, program)
    assert hlo.startswith(f"HloModule jit_{program},")
    made = re.compile(r"(?:ROOT )?%[\w.-]+ = " + re.escape(pool)
                      + r"\{[^}]*\} ([\w-]+)\(")
    comps = _computations(hlo)
    ops = [m.group(1) for body in comps.values()
           for m in map(made.match, body) if m]
    assert "dynamic-update-slice" in ops
    relayouts = [op for op in ops if op.startswith("copy") or op == "scatter"]
    assert not relayouts, relayouts
    header = hlo.split("entry_computation_layout=", 1)[0]
    aliased = {int(n) for n in re.findall(r"\{\d+\}: \((\d+), ", header)}
    pool_params = {int(m.group(1)) for ins in comps["ENTRY"]
                   for m in [re.search(re.escape(pool)
                                       + r"\{[^}]*\} parameter\((\d+)\)",
                                       ins)] if m}
    assert len(pool_params) == 1 and pool_params <= aliased
    shape = re.compile(r"= \w+\[([\d,]+)\]")
    for body in comps.values():
        for ins in body:
            m = shape.search(ins)
            dims = set(map(int, m.group(1).split(","))) if m else set()
            assert not ({1024, 16} <= dims and dims & {128, 192}), ins[:120]


def test_latent_decode_step_names_its_kernels(one_chip, monkeypatch):
    """The decode step holds one latent-attention call per layer loop
    (the dense layer's and the MoE layers'), which the benchmark's
    ``mla_decode`` matcher claims, and one routed-expert call, which the
    ``moe_ffn`` matcher claims; neither claims the shared experts' call,
    the flash kernel's name or the ops that take their results."""
    from pathlib import Path
    monkeypatch.syspath_prepend(
        str(Path(__file__).resolve().parents[1] / "bench"))
    from work import mla_decode, moe_ffn
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, _ = _latent_step_hlo(one_chip, "decode_step")
    ins = [i for body in _computations(hlo).values() for i in body
           if i.startswith(("%", "ROOT %"))]
    mla = [i for i in ins if mla_decode.matches(i)]
    moe = [i for i in ins if moe_ffn.matches(i)]
    assert len(mla) == 2 and all("%mla_decode." in i.split(" = ")[0]
                                 for i in mla)
    assert len(moe) == 1 and "%moe_experts." in moe[0].split(" = ")[0]
    assert any("%moe_shared." in i.split(" = ")[0] for i in ins)
    assert not any("flash_splits" in i for i in ins)
