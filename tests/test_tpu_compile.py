"""Compile the TPU engine room for a described v5e, without a chip.

XLA:TPU refuses complex128 (and aborts the process on some of it) and
has no uint64 `dot`; Mosaic refuses blocks off the (8, 128) tiling and
kernels that overflow VMEM.  Interpret mode on the CPU shows none of
that, so these tests lower the main path's programs and the Pallas
kernels for one chip of a `v5e:2x2` topology and compile them with the
TPU compiler that ships with libtpu.  Each program's jaxpr is checked
for complex values and uint64 dots BEFORE it is compiled, so a
regression fails here with a message instead of a SIGABRT.

The topology is described inside a module fixture (never at import:
only one process at a time may load libtpu, and every test worker
imports this file), and the persistent compile cache is off in this
file: a compile for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import batch, pbs
from repro.core.params import PAPER_PARAMS, TEST_PARAMS_4BIT
from repro.kernels import external_product, fourstep_fft, keyswitch

B = 12                       # the engine's fused round width per device
HBM_BYTES = 16 * 10 ** 9     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no libtpu
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _engine_args(params, sharding):
    """Round inputs plus the evaluation keys as keygen lays them out
    (shapes and dtypes from `jax.eval_shape`, nothing materialized)."""
    keys = jax.eval_shape(lambda k: pbs._keygen(k, params),
                          jax.random.key(0))
    bsk_f, ksk = keys[3], keys[4]
    assert bsk_f.dtype == jnp.float64, f"BSK is {bsk_f.dtype}, not f64 planes"
    assert ksk.dtype == jnp.uint64
    u64 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint64)
    return _on(sharding, dict(
        cts=u64(B, params.big_n + 1), small=u64(B, params.n + 1),
        polys=u64(B, params.N), bsk_f=bsk_f, ksk=ksk))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _assert_tpu_types(fn, *args):
    """No complex value anywhere, and no dot over uint64 operands."""
    closed = jax.make_jaxpr(fn)(*args)
    for eqn in _walk(closed.jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            dt = getattr(v.aval, "dtype", None)
            assert dt is None or not jnp.issubdtype(dt, jnp.complexfloating), (
                f"{eqn.primitive} carries {dt}: XLA:TPU has no complex128")
        if eqn.primitive.name == "dot_general":
            dts = {v.aval.dtype for v in eqn.invars}
            assert jnp.dtype(jnp.uint64) not in dts, (
                f"dot_general over {dts}: XLA:TPU has no uint64 dot")


def _compile(fn, *args):
    _assert_tpu_types(fn, *args)
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "c128" not in hlo
    return compiled, hlo


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)


@pytest.mark.parametrize("entry", ["lut_batch", "keyswitch",
                                   "lut_batch_small"])
def test_engine_room_compiles_test4bit(one_chip, entry):
    p = TEST_PARAMS_4BIT
    a = _engine_args(p, one_chip)
    fn, args = {
        "lut_batch": (lambda c, l, b, k: batch.pbs_batch(c, l, b, k, p),
                      (a["cts"], a["polys"], a["bsk_f"], a["ksk"])),
        "keyswitch": (lambda c, k: batch.keyswitch_batch_jit(c, k, p),
                      (a["cts"], a["ksk"])),
        "lut_batch_small": (lambda c, l, b: batch.pbs_batch_small(c, l, b, p),
                            (a["small"], a["polys"], a["bsk_f"])),
    }[entry]
    compiled, _ = _compile(fn, *args)
    assert _device_bytes(compiled) < HBM_BYTES


def test_lut_batch_fits_one_chip_at_cnn20(one_chip):
    p = PAPER_PARAMS["cnn20"]
    a = _engine_args(p, one_chip)
    compiled, _ = _compile(
        lambda c, l, b, k: batch.pbs_batch(c, l, b, k, p),
        a["cts"], a["polys"], a["bsk_f"], a["ksk"])
    used = _device_bytes(compiled)
    assert used < HBM_BYTES, f"cnn20 round needs {used} B on a 16 GB chip"


def test_pallas_kernels_compile_f32_n2048(one_chip):
    """The three kernels ROADMAP S2 starts from, Mosaic-compiled at f32
    for N = 2048 (test-4bit / cnn20 ring size)."""
    N, M, J, K, S, T = 2048, 1024, 4, 2, 2048 * 5, 97
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32,
                                              sharding=one_chip)
    cases = [
        (lambda x: fourstep_fft.fft_forward(x, interpret=False),
         (f32(B * J, N),)),
        (lambda s: fourstep_fft.fft_inverse(s, interpret=False),
         (f32(B * K, 2, M),)),
        (lambda d, w: external_product.external_product_mac(
            d, w, interpret=False), (f32(B, 2, J, M), f32(2, J, K, M))),
        (lambda d, h, l: keyswitch.keyswitch_mac(d, h, l, interpret=False),
         (i32(B, S), u32(S, T), u32(S, T))),
    ]
    for fn, args in cases:
        _, hlo = _compile(fn, *args)
        assert "tpu_custom_call" in hlo, "kernel did not lower to Mosaic"
