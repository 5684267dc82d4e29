"""Sharded serving (ISSUE 10): the router/EngineShard split, elastic
admission, device assignment, KS-level dedup, and the pallas+mesh
route-around.

Decrypt-parity tests pin the tentpole's core contract: shards=1 is
indistinguishable (after decryption) from the pre-shard runtime, and
shards=2 from shards=1.  Queue-level tests use linear-only programs so
they spend no PBS time (same convention as tests/test_serve.py).
"""
import time

import numpy as np
import pytest

import jax

from repro.compiler.ir import trace
from repro.core import glwe
from repro.core.engine import TaurusEngine
from repro.core.integer import IntegerContext
from repro.launch.mesh import shard_devices, shard_mesh
from repro.runtime.elastic import ElasticAdmission, ElasticPolicy
from repro.serve import (ConfigError, ServeRuntime, build_shards,
                         decrypt_radix_output, encrypt_request_inputs,
                         radix_binop_program, radix_unop_program)
from repro.sim.arrivals import MMPP, arrival_plan

BITS = 8


@pytest.fixture()
def ic4(ctx_4bit, engine_4bit):
    return IntegerContext.create(ctx_4bit, engine_4bit)


def _linear_graph(const):
    """PBS-free program: (x + const) on a 1-element tensor."""
    return trace(lambda x: x + np.array([const]), (1,))


# --- ElasticAdmission: pure controller unit tests ---------------------------

def test_elastic_policy_validation():
    with pytest.raises(ValueError, match="floor"):
        ElasticPolicy(ceiling=2, floor=3)
    with pytest.raises(ValueError, match="floor"):
        ElasticPolicy(floor=0)
    with pytest.raises(ValueError, match="step"):
        ElasticPolicy(step_up=0)


def test_elastic_admission_grow_shrink_unit():
    el = ElasticAdmission(ElasticPolicy(ceiling=4, floor=1))
    assert el.limit == 1
    # backlog + saturated slots: grow one step at a time, never past
    # the ceiling
    for want in (2, 3, 4):
        assert el.observe(queue_depth=5, inflight=el.limit) is True
        assert el.limit == want
    assert el.observe(queue_depth=5, inflight=4) is False   # at ceiling
    assert el.high_water == 4 and el.grows == 3
    # backlog but idle slots: not a grow opportunity
    el2 = ElasticAdmission(ElasticPolicy(ceiling=4, floor=1))
    assert el2.observe(queue_depth=5, inflight=0) is False
    # low occupancy vetoes growth; a healthy signal permits it
    assert el.observe(queue_depth=5, inflight=4, occupancy=0.2) is False
    el3 = ElasticAdmission(ElasticPolicy(ceiling=4, floor=1))
    assert el3.observe(queue_depth=1, inflight=1, occupancy=0.9) is True
    # empty queue + idle slots: decay toward max(floor, inflight)
    assert el.observe(queue_depth=0, inflight=2) is True
    assert el.limit == 3                     # never cuts below running work
    assert el.observe(queue_depth=0, inflight=0) is True
    assert el.observe(queue_depth=0, inflight=0) is True
    assert el.limit == 1 and el.shrinks == 3
    assert el.observe(queue_depth=0, inflight=0) is False   # at floor


# --- device -> shard assignment ---------------------------------------------

def test_shard_devices_and_mesh():
    devs = jax.devices()
    with pytest.raises(ValueError, match=">= 1"):
        shard_devices(0)
    # oversubscription: fewer devices than shards round-robins
    sets = shard_devices(3)
    assert len(sets) == 3 and all(len(s) == 1 for s in sets)
    assert [s[0] for s in sets] == [devs[i % len(devs)] for i in range(3)]
    # exact fit: one device per shard
    one = shard_devices(len(devs))
    assert [s[0] for s in one] == list(devs)
    m = shard_mesh(one[0])
    assert m.devices.shape == (1,) and m.axis_names == ("data",)


# --- engine level: ConfigError + the keyswitch/lut_batch_small split --------

def test_engine_mesh_pallas_config_error(ctx_2bit):
    mesh = shard_mesh((jax.devices()[0],))
    with pytest.raises(ConfigError, match="pallas"):
        TaurusEngine.from_context(ctx_2bit, mesh=mesh,
                                  kernel_backend="pallas")
    # typed AND backward compatible: ConfigError is a ValueError
    assert issubclass(ConfigError, ValueError)


def test_engine_ks_split_matches_lut_batch(ctx_4bit, engine_4bit):
    """keyswitch + lut_batch_small composes to exactly lut_batch —
    the arithmetic identity KS-level dedup rests on."""
    params = ctx_4bit.params
    mod = params.plaintext_modulus
    xs = np.array([0, 3, 7, 11], dtype=np.uint64) % mod
    cts = ctx_4bit.encrypt(jax.random.key(70), xs)
    tables = np.stack([(np.arange(mod, dtype=np.uint64) + i) % mod
                       for i in range(len(xs))])
    full = engine_4bit.lut_batch_tables(cts, tables)
    small = engine_4bit.keyswitch(cts)
    split = engine_4bit.lut_batch_small(
        small, glwe.make_lut_polys_cached(tables, params))
    np.testing.assert_array_equal(np.asarray(full)[:len(xs)],
                                  np.asarray(split)[:len(xs)])
    got = [int(ctx_4bit.decrypt(r)) for r in np.asarray(split)[:len(xs)]]
    assert got == [int((x + i) % mod) for i, x in enumerate(xs)]


# --- scheduler level: KS dedup on/off decrypt parity ------------------------

def _serve_wave(ctx, engine, jobs, **kw):
    rt = ServeRuntime(ctx, engine, fused=True, max_inflight=len(jobs),
                      start_paused=True, **kw)
    handles = [rt.submit(g, enc, client_id=c) for c, g, enc in jobs]
    rt.resume()
    rt.drain()
    return rt, [h.outputs()[0] for h in handles]


def test_ks_dedup_on_off_decrypts_identical(ctx_4bit, engine_4bit, ic4):
    """A radix-add wave batches [digits, digits] against [msg, carry]
    tables every ripple round — guaranteed same-ciphertext rows, so
    KS-level dedup must fire, and turning it off must not change a
    single decrypted value."""
    m = ic4.spec(BITS).msg_bits
    g = radix_binop_program("radix_add", BITS, m)
    rng = np.random.default_rng(13)
    jobs, wants = [], []
    for i in range(3):
        a, b = int(rng.integers(0, 256)), int(rng.integers(0, 256))
        enc = encrypt_request_inputs(ic4, jax.random.key(90 + i), [a, b],
                                     BITS)
        jobs.append((f"client-{i}", g, enc))
        wants.append((a + b) % 256)

    rt_on, outs_on = _serve_wave(ctx_4bit, engine_4bit, jobs, ks_dedup=True)
    rt_off, outs_off = _serve_wave(ctx_4bit, engine_4bit, jobs,
                                   ks_dedup=False)
    for o_on, o_off, want in zip(outs_on, outs_off, wants):
        assert decrypt_radix_output(ic4, o_on, BITS)[0] == want
        assert decrypt_radix_output(ic4, o_off, BITS)[0] == want
    assert rt_on.scheduler.stats["ks_dedup_hits"] > 0
    assert rt_off.scheduler.stats["ks_dedup_hits"] == 0
    # KS dedup shares keyswitches, not whole-row dispatches: the fused
    # round structure is unchanged
    assert (rt_on.scheduler.stats["fused_rounds"]
            == rt_off.scheduler.stats["fused_rounds"])
    assert (rt_on.scheduler.stats["dispatched_luts"]
            == rt_off.scheduler.stats["dispatched_luts"])


# --- router: shards=1 vs shards=2 decrypt parity + per-shard metrics --------

def test_sharded_decrypt_parity_and_metrics(ctx_4bit, engine_4bit, ic4):
    m = ic4.spec(BITS).msg_bits
    rng = np.random.default_rng(17)
    jobs, wants = [], []
    for i, op in enumerate(("radix_add", "radix_mul", "radix_add",
                            "radix_sub")):
        g = radix_binop_program(op, BITS, m)
        a, b = int(rng.integers(0, 256)), int(rng.integers(0, 256))
        enc = encrypt_request_inputs(ic4, jax.random.key(110 + i), [a, b],
                                     BITS)
        jobs.append((f"client-{i}", g, enc))
        oracle = {"radix_add": a + b, "radix_mul": a * b,
                  "radix_sub": a - b}[op]
        wants.append(oracle % 256)
    g_relu = radix_unop_program("radix_relu", BITS, m)
    enc = encrypt_request_inputs(ic4, jax.random.key(115), [-7], BITS)
    jobs.append(("client-4", g_relu, enc))
    wants.append(0)

    rt1, outs1 = _serve_wave(ctx_4bit, engine_4bit, jobs, shards=1)
    rt2, outs2 = _serve_wave(ctx_4bit, engine_4bit, jobs, shards=2)
    for o1, o2, want in zip(outs1, outs2, wants):
        assert decrypt_radix_output(ic4, o1, BITS)[0] == want
        assert decrypt_radix_output(ic4, o2, BITS)[0] == want

    # both shards did real work, and the per-shard namespace is complete
    c2 = rt2.metrics()["counters"]
    for i in (0, 1):
        assert c2[f"serve.shard.{i}.admitted"] > 0
        assert c2[f"serve.shard.{i}.completed"] > 0
        assert c2[f"serve.shard.{i}.fused_rounds"] > 0
        assert f"serve.shard.{i}.ks_dedup_hits" in c2
        assert c2[f"serve.shard.{i}.bsk_bytes_streamed"] > 0
    assert (c2["serve.shard.0.admitted"] + c2["serve.shard.1.admitted"]
            == len(jobs))
    # shards=1 mirrors the same namespace for shard 0 only
    c1 = rt1.metrics()["counters"]
    assert c1["serve.shard.0.admitted"] == len(jobs)
    assert "serve.shard.1.admitted" not in c1


def test_router_balances_and_no_client_starves(ctx_2bit, engine_2bit):
    """Least-loaded placement spreads a linear-program wave across both
    shards, and the router's round-robin client fairness survives the
    shard split: a flooding client cannot starve the others."""
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False, shards=2,
                      max_inflight=1, start_paused=True)
    g = _linear_graph(1)
    x = ctx_2bit.encrypt(jax.random.key(120), np.array([1]))
    handles = {}
    for i in range(4):                       # client A floods first
        handles[("A", i)] = rt.submit(g, [x], client_id="A")
    handles[("B", 0)] = rt.submit(g, [x], client_id="B")
    handles[("C", 0)] = rt.submit(g, [x], client_id="C")
    rt.resume()
    rt.drain()
    order = rt.stats["admitted"]
    assert len(order) == 6
    pos = {cid: [i for i, (c, _) in enumerate(order) if c == cid]
           for cid in "ABC"}
    n_clients = 3
    assert pos["B"][0] < n_clients
    assert pos["C"][0] < n_clients
    counters = rt.metrics()["counters"]
    assert counters["serve.shard.0.admitted"] > 0
    assert counters["serve.shard.1.admitted"] > 0
    for h in handles.values():
        assert int(ctx_2bit.decrypt(h.outputs()[0][0])) == 2


def test_build_shards_validation(ctx_2bit, engine_2bit):
    with pytest.raises(ConfigError, match=">= 1"):
        build_shards(ctx_2bit, engine_2bit, n_shards=0)
    with pytest.raises(ConfigError, match="device_sets"):
        build_shards(ctx_2bit, engine_2bit, n_shards=2,
                     device_sets=[(jax.devices()[0],)])
    with pytest.raises(TypeError, match="elastic"):
        build_shards(ctx_2bit, engine_2bit, n_shards=1, elastic="yes")


def test_pallas_shards_route_around_mesh(ctx_2bit, pallas_engine_2bit):
    """A multi-device shard asking for pallas is the documented
    ConfigError combination — build_shards routes around it at
    construction by pinning the shard to a single-device pallas engine,
    and the resulting runtime still serves correctly."""
    dev = jax.devices()[0]
    shards = build_shards(ctx_2bit, pallas_engine_2bit, n_shards=2,
                          device_sets=[(dev,), (dev, dev)])
    assert shards[1].engine.kernel_backend == "pallas"
    assert shards[1].engine.mesh is None       # routed around, not crashed
    assert shards[1].engine is not shards[0].engine

    rt = ServeRuntime(ctx_2bit, pallas_engine_2bit, fused=False, shards=2,
                      max_inflight=1, start_paused=True)
    assert all(s.engine.kernel_backend == "pallas" for s in rt.shards)
    g = _linear_graph(2)
    x = ctx_2bit.encrypt(jax.random.key(130), np.array([1]))
    handles = [rt.submit(g, [x], client_id=f"c{i}") for i in range(3)]
    rt.resume()
    rt.drain()
    for h in handles:
        assert int(ctx_2bit.decrypt(h.outputs()[0][0])) == 3


@pytest.mark.slow
def test_sharded_gpt2_block_parity(ctx_4bit, engine_4bit):
    """The ISSUE 10 acceptance's heavy workload: a quantized GPT-2-style
    block (ct*ct attention, ReLU MLP) served with shards=2 decrypts to
    exactly the eager backend's values — encrypted-transformer traffic
    survives the router/shard split bit-for-bit."""
    from repro.api import Session
    from repro.fhe_ml import lower
    from repro.fhe_ml.quantize import calibrate_radix, quantize_to_radix

    g, meta = lower.lower_gpt2_block_radix(2, bits=16, msg_bits=2, seed=1)
    rng = np.random.default_rng(3)
    xf = rng.uniform(-1, 1, size=(2,))
    rq = calibrate_radix(xf, 16, 2, qmax=meta["input_qmax"])
    q = quantize_to_radix(xf, rq)
    want = meta["int_fn"](q) % (1 << 16)
    outs = {}
    for label, kw in (("eager", {"backend": "eager"}),
                      ("serve2", {"backend": "serve", "shards": 2})):
        with Session(ctx_4bit, engine_4bit, **kw) as sess:
            prog = sess.compile(g, meta["in_specs"], meta["out_specs"])
            outs[label] = np.asarray(sess(prog, jax.random.key(7), q)[0])
    np.testing.assert_array_equal(outs["eager"] % (1 << 16), want)
    np.testing.assert_array_equal(outs["eager"], outs["serve2"])


# --- elastic admission under live traffic -----------------------------------

def test_elastic_mmpp_burst_ramps_and_decays(ctx_2bit, engine_2bit):
    """An MMPP calm->burst arrival stream against one elastic shard:
    the limit ramps above the floor during the burst, never exceeds the
    ceiling, and decays back to the floor once the burst drains."""
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False, elastic=True,
                      max_inflight=4)
    el = rt.shards[0].elastic
    assert el is not None and el.limit == el.policy.floor == 1
    assert el.policy.ceiling == 4

    g = _linear_graph(1)
    x = ctx_2bit.encrypt(jax.random.key(140), np.array([2]))
    # calm 1.0 virtual-s at 4 rps, then a 0.5 virtual-s burst at 80 rps
    plan = arrival_plan(MMPP(((4.0, 1.0), (80.0, 0.5))), population=3,
                        duration_s=1.5, seed=7)
    assert len(plan) > 10                      # the burst actually burst
    scale = 0.02
    t0 = time.perf_counter()
    handles = []
    for t_v, client in plan:
        delay = t_v * scale - (time.perf_counter() - t0)
        if delay > 0:
            time.sleep(delay)
        handles.append(rt.submit(g, [x], client_id=f"c{client}"))
    rt.drain()

    assert el.high_water > el.policy.floor      # ramped up under backlog
    assert el.high_water <= el.policy.ceiling   # never exceeded the ceiling
    assert el.grows >= 1 and el.shrinks >= 1
    assert el.limit == el.policy.floor          # decayed after the burst
    assert rt.stats["completed"] == len(handles)
    for h in handles[:3] + handles[-3:]:
        assert int(ctx_2bit.decrypt(h.outputs()[0][0])) == 3


def test_elastic_cross_shard_fairness(ctx_2bit, engine_2bit):
    """Two elastic shards under a burst: each shard runs its OWN
    controller (limits move independently, both bounded by the shared
    ceiling), both shards take work, and no client starves."""
    rt = ServeRuntime(ctx_2bit, engine_2bit, fused=False, shards=2,
                      elastic=True, max_inflight=2, start_paused=True)
    g = _linear_graph(2)
    x = ctx_2bit.encrypt(jax.random.key(150), np.array([1]))
    handles = {}
    for i in range(6):                       # client A floods first
        handles[("A", i)] = rt.submit(g, [x], client_id="A")
    handles[("B", 0)] = rt.submit(g, [x], client_id="B")
    handles[("C", 0)] = rt.submit(g, [x], client_id="C")
    rt.resume()
    rt.drain()

    controllers = [s.elastic for s in rt.shards]
    assert controllers[0] is not controllers[1]
    for el in controllers:
        assert el.high_water <= el.policy.ceiling == 2
        assert el.limit == el.policy.floor
    order = rt.stats["admitted"]
    pos = {cid: [i for i, (c, _) in enumerate(order) if c == cid]
           for cid in "ABC"}
    assert pos["B"][0] < 3 and pos["C"][0] < 3
    counters = rt.metrics()["counters"]
    assert counters["serve.shard.0.admitted"] > 0
    assert counters["serve.shard.1.admitted"] > 0
    for h in handles.values():
        assert int(ctx_2bit.decrypt(h.outputs()[0][0])) == 3


PLACEMENT_SCRIPT = """
import jax
import numpy as np
from repro.core.integer import IntegerContext
from repro.core.params import TEST_PARAMS
from repro.core.pbs import TFHEContext
from repro.serve import ServeRuntime
from repro.serve.programs import (decrypt_radix_output,
                                  encrypt_request_inputs, radix_binop_program)

devs = jax.devices()
assert len(devs) == 4, devs
ctx = TFHEContext.create(jax.random.key(60), TEST_PARAMS)
ic = IntegerContext.create(ctx)
m = ic.spec(8).msg_bits
g = radix_binop_program("radix_add", 8, m)
rng = np.random.default_rng(61)
pairs = [tuple(int(v) for v in rng.integers(0, 256, 2)) for _ in range(8)]
jobs = [encrypt_request_inputs(ic, jax.random.key(62 + i), list(p), 8)
        for i, p in enumerate(pairs)]

decrypts = {}
for shards in (4, 1):
    rt = ServeRuntime(ctx, None, shards=shards, max_inflight=2,
                      start_paused=True)
    handles = [rt.submit(g, enc, client_id=f"c{i}")
               for i, enc in enumerate(jobs)]
    rt.resume()
    rt.drain()
    outs = [h.outputs()[0] for h in handles]
    decrypts[shards] = [decrypt_radix_output(ic, o, 8)[0] for o in outs]
    if shards == 4:
        for i, sh in enumerate(rt.shards):
            assert sh.engine.mesh is None
            assert sh.engine.bsk_f.devices() == {devs[i]}, i
            assert sh.engine.ksk.devices() == {devs[i]}, i
        for h, o in zip(handles, outs):
            assert o.devices() == {devs[h.shard]}, (h.shard, o.devices())
        assert {h.shard for h in handles} == {0, 1, 2, 3}
    else:
        assert rt.shards[0].engine.mesh is not None
        assert set(rt.shards[0].engine.mesh.devices.flat) == set(devs)
    rt.close()
assert decrypts[4] == decrypts[1] == [(a + b) % 256 for a, b in pairs]
print("PLACEMENT_OK")
"""


@pytest.mark.slow
def test_four_shards_place_keys_rounds_and_outputs_on_own_devices():
    """On four (virtual CPU) devices, `shards=4` pins every shard's BSK,
    KSK and outputs to its own device, and decrypts exactly like
    `shards=1`, the one shard spanning all four devices as a mesh."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", PLACEMENT_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "PLACEMENT_OK" in r.stdout
