"""``chip_smoke.py``'s phases at the smoke config on the CPU.

The script serves Qwen1.5-4B at full width on a TPU; here the same phase
functions run a small same-family config with interpret-mode kernels, so
a broken phase shows up before any chip time is spent. The device check
is the one part not run: it must refuse the CPU, which the subprocess
test checks.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from conftest import run_in_devices  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("qwen1.5-4b")


def test_one_chip_phases_at_smoke_size(cfg, capsys):
    reqs = cs.make_requests(cfg, 0, n=4, lo=8, hi=40, max_new=4)
    # the kernels named explicitly: on the CPU "auto" picks the oracles
    cs.one_chip(cfg, 0, reqs, batch_size=4, max_seq=128,
                qc=cs.LUT_QC.replace(impl="fused", flash="pallas"))
    out = capsys.readouterr().out
    for phase in ("[dense]", "[convert]", "[lut]", "[layer_parity]",
                  "[parity]"):
        assert phase in out
    assert out.count("requests=4/4") == 2
    assert out.count("[layer_parity]") == 2 * cfg.num_layers
    # on the CPU the kernels and the oracles agree exactly
    model = cs.Model(cfg)
    lut = cs.convert(model.init(cs.jax.random.PRNGKey(1), cs.LUT_QC))
    errs, top1 = cs.model_parity(model, lut, cs.LUT_QC, reqs[0].tokens[:8])
    assert max(errs.values()) <= 1e-5 and all(top1.values())


def test_parity_catches_a_wrong_kernel(cfg, monkeypatch):
    """A kernel that is off by a few percent fails the bound."""
    real = cs.vq_amm_pallas
    monkeypatch.setattr(cs, "vq_amm_pallas",
                        lambda *a, **k: real(*a, **k) * 1.05)
    errs = cs.kernel_parity(cfg, 0, rows=(8,))
    assert all(e > cs.KERNEL_TOL for k, e in errs.items()
               if k.startswith("vq_amm"))


@pytest.mark.parametrize("fault", ["wrong_centroid", "scaled_output"])
def test_layer_parity_catches_a_wrong_kernel(cfg, monkeypatch, fault):
    """A kernel that picks centroids away from a tie, or whose lookups
    are off by 5%, fails the per-layer bounds."""
    from repro.kernels import ops
    real = cs.vq_amm_pallas
    if fault == "wrong_centroid":
        bad = lambda x, *a, **k: real(cs.jnp.roll(x, 1, axis=-1), *a, **k)
    else:
        bad = lambda *a, **k: real(*a, **k) * 1.05
    monkeypatch.setattr(cs, "vq_amm_pallas", bad)
    monkeypatch.setattr(ops, "vq_amm_pallas", bad)
    one = cfg.replace(num_layers=1)
    model = cs.Model(one)
    lut = cs.convert(model.init(cs.jax.random.PRNGKey(2), cs.LUT_QC))
    rows = cs.layer_parity(model, lut, cs.LUT_QC.replace(impl="fused"),
                           list(range(1, 9)), batch_size=2)
    if fault == "wrong_centroid":
        assert all(r["max_tie_ratio"] > cs.TIE_ULPS
                   and r["layer_rel_err"] > cs.LAYER_TOL for r in rows)
    else:
        assert all(r["max_lookup_gap"] > 0 for r in rows)


def test_mesh_phase_on_four_host_devices():
    """The ``--chips 4`` path on a (2, 2) mesh of host devices, with the
    Pallas kernels (interpret mode) run per shard under shard_map: the
    TP-2 replicas match one device token for token, and the sharded
    kernels match the unsharded ones exactly."""
    out = run_in_devices(f"""
import sys
sys.path.insert(0, {ROOT!r})
import jax, jax.numpy as jnp
import chip_smoke as cs
from repro.configs import get_smoke_config
from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode_paged
from repro.launch.mesh import make_test_mesh

cfg = get_smoke_config("qwen1.5-4b")
qc = cs.LUT_QC.replace(impl="fused", flash="pallas")
reqs = cs.make_requests(cfg, 0, n=4, lo=8, hi=40, max_new=4)
cs.mesh_phase(cfg, 0, reqs, batch_size=4, max_seq=128, qc=qc)

mesh = make_test_mesh((2, 2), ("data", "model"))
k = jax.random.split(jax.random.PRNGKey(0), 8)
x = jax.random.normal(k[0], (8, 32, 4))
z = jax.random.normal(k[1], (32, 8, 4))
lut8 = jax.random.randint(k[2], (32, 8, 64), -127, 128).astype(jnp.int8)
scale = jax.random.uniform(k[3], (64,))
want = ops.vq_amm(x, z, lut8, scale, impl="fused")
for tp in ("col", "row"):
    with jax.set_mesh(mesh):
        got = jax.jit(lambda *a: ops.vq_amm(*a, impl="fused", tp=tp))(
            x, z, lut8, scale)
    assert bool((got == want).all()), tp
# with no shardable role a Pallas kernel would run whole on every chip
with jax.set_mesh(mesh):
    try:
        jax.jit(lambda *a: ops.vq_amm(*a, impl="fused"))(x, z, lut8, scale)
    except NotImplementedError:
        pass
    else:
        raise AssertionError("vq_amm ran unsharded under a model axis")
kp = jax.random.normal(k[4], (2, 9, 4, 8, 16))  # 2 layers, head-major
q = jax.random.normal(k[5], (2, 1, 8, 16))
kn = jax.random.normal(k[6], (2, 1, 4, 16))
phys = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
pos = jnp.array([5, 20], jnp.int32)
args = (q, kp, kp * 0.5, kn, kn * 2.0, phys, pos)
flash = jax.jit(lambda *a: flash_decode_paged(*a, layer=1, impl="pallas",
                                              interpret=True))
want = flash(*args)
with jax.set_mesh(mesh):
    got = flash(*args)
err = float(jnp.max(jnp.abs(got - want)))
assert err <= 1e-6, err
print("MESH_OK")
""")
    assert "token_match=4/4" in out and "MESH_OK" in out


def test_largest_all_gather_parses_hlo():
    hlo = ("  %ag = s8[40,320,16,2560]{3,2,1,0} all-gather(s8[40,320,16,1280]"
           "{3,2,1,0} %p), dimensions={3}\n"
           "  %x = f32[8,128]{1,0} add(f32[8,128] %a, f32[8,128] %b)\n")
    assert cs.largest_all_gather(hlo) == 40 * 320 * 16 * 2560
    assert cs.largest_all_gather("  %x = f32[8]{0} negate(f32[8] %a)") == 0


def test_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
