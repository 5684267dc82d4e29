"""The trace reduction, on a 400 ms trace recorded on a v5e (last test)
and on a small synthetic trace laid out as the v5e's profiler writes it: a `/device:TPU:0` plane with "XLA Modules" events named
`jit_<program>(<fingerprint>)` and "XLA Ops" events named by their HLO
text, and a `/host:CPU` plane with the harness's annotations.  Times below
are nanoseconds; every expected number is worked out by hand."""
import jax
import pytest

import trace_reduce

US = 1e-6


def _plane(pid, name, line, events):
    names = sorted({n for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    evs = "\n".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
        f"duration_ps: {(e - s) * 1000} }}" for n, s, e in events)
    meta = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for n, i in ids.items())
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n'
            f'  lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}\n  }}\n'
            f"{meta}\n}}\n")


def _device(pid):
    modules = [("jit_pbs_batch_small(11)", 5_000, 15_000),
               ("jit_keyswitch_batch_jit(22)", 30_000, 40_000),
               ("jit_pbs_batch_small(11)", 38_000, 60_000),
               ("jit_pbs_batch(33)", 100_000, 130_000)]
    ops = [("%fusion.1 = u32[16,97] fusion(u32[16,2048,5] %p)", 31_000,
            39_000),
           ("%while.7 = (f64[16,2,2,1024]) while(%t)", 40_000, 59_000),
           ("%copy.3 = u64[16,2049] copy(%x)", 70_000, 71_000)]
    mod = _plane(pid, "/device:TPU:0", "XLA Modules", modules)
    op = _plane(pid, "/device:TPU:0", "XLA Ops", ops)
    # one plane with two lines: splice the second line into the first
    line2 = op[op.index("  lines"):op.index("  event_metadata")]
    meta2 = op[op.index("  event_metadata"):].rsplit("}", 1)[0]
    meta2 = meta2.replace("key: 1 value { id: 1", "key: 11 value { id: 11") \
        .replace("key: 2 value { id: 2", "key: 12 value { id: 12") \
        .replace("key: 3 value { id: 3", "key: 13 value { id: 13")
    line2 = line2.replace("id: 1 name", "id: 2 name") \
        .replace("metadata_id: 1 ", "metadata_id: 11 ") \
        .replace("metadata_id: 2 ", "metadata_id: 12 ") \
        .replace("metadata_id: 3 ", "metadata_id: 13 ")
    head, tail = mod.rsplit("}", 1)
    return head + line2 + meta2 + "}" + tail


@pytest.fixture(scope="module")
def pd():
    host = _plane(1, "/host:CPU", "python", [
        ("bench.anchor", 0, 1_000), ("bench.traced", 10_000, 110_000),
        ("bench.submit", 62_000, 64_000)])
    return jax.profiler.ProfileData.from_text_proto(host + _device(2))


@pytest.fixture(scope="module")
def out(pd):
    spans = [("request", 100.0, 110 * US),
             ("fused_round", 100.0 + 58 * US, 32 * US)]
    return trace_reduce.reduce(pd, spans, anchor_pc=100.0)


def test_window_and_busy(out):
    assert out["window_s"] == pytest.approx(100 * US)
    # [10, 15] + [30, 60] (two overlapping programs) + [100, 110] us
    assert out["busy_s"] == pytest.approx(45 * US)
    assert out["devices"] == 1


def test_programs(out):
    # only executions wholly inside the window count: [5, 15] and
    # [100, 130] are cut by its edges
    progs = out["programs"]
    assert set(progs) == {"pbs_batch_small", "keyswitch_batch_jit"}
    assert progs["pbs_batch_small"]["seconds"] == pytest.approx(22 * US)
    assert progs["pbs_batch_small"]["count"] == 1
    assert progs["keyswitch_batch_jit"]["seconds"] == pytest.approx(10 * US)
    assert progs["keyswitch_batch_jit"]["count"] == 1


def test_device_ops_named_by_program(out):
    names = [n for n, _ in out["device_ops"]]
    assert names == ["pbs_batch_small/while.7", "keyswitch_batch_jit/fusion.1",
                     "copy.3"]
    assert [t for _, t in out["device_ops"]] == pytest.approx(
        [19 * US, 8 * US, 1 * US])


def test_idle_gaps_named_by_host_activity(out):
    # [60, 100] is covered by the mapped fused_round span (30 of 40 us),
    # shorter than the request span; [15, 30] only by the request
    assert [n for n, _ in out["idle_gaps"]] == ["fused_round", "request"]
    assert [t for _, t in out["idle_gaps"]] == pytest.approx(
        [40 * US, 15 * US])


def test_no_window_annotation_is_an_error():
    host = _plane(1, "/host:CPU", "python", [("bench.anchor", 0, 1_000)])
    pd = jax.profiler.ProfileData.from_text_proto(host + _device(2))
    with pytest.raises(ValueError):
        trace_reduce.reduce(pd)


def test_program_names():
    assert trace_reduce.program_name("jit_pbs_batch_small(5297093306)") == \
        "pbs_batch_small"
    assert trace_reduce.program_name("jit_keyswitch_batch_jit") == \
        "keyswitch_batch_jit"


RECORDED = __file__.rsplit("/", 1)[0] + "/data/solo-400ms.xplane.pb.gz"


def test_recorded_chip_trace():
    """The first 400 ms of the traced window of `radix16-msg2carry2.solo`
    as a TPU v5 lite recorded it (jax 0.9.0), cut down to its device and
    host planes.  The round's `pbs_batch_small` runs on past the cut: it
    counts as busy time but not as a program execution."""
    out = trace_reduce.reduce(trace_reduce.load(RECORDED))
    assert out["window_s"] == pytest.approx(0.4)
    assert out["busy_s"] == pytest.approx(0.365047011)
    assert out["devices"] == 1
    progs = out["programs"]
    assert "pbs_batch_small" not in progs
    assert progs["keyswitch_batch_jit"]["count"] == 1
    assert progs["keyswitch_batch_jit"]["seconds"] == pytest.approx(
        0.003683407)
    assert sum(p["count"] for p in progs.values()) == 59
    ops = out["device_ops"]
    assert [n for n, _ in ops[:2]] == ["pbs_batch_small/fusion.1627",
                                       "pbs_batch_small/fusion.1626"]
    assert ops[0][1] == pytest.approx(0.087233197)
    gaps = out["idle_gaps"]
    assert gaps[0][1] == pytest.approx(0.013970099)
    assert [n for n, _ in gaps[:2]] == ["no host span", "bench.submit"]
    assert sum(t for _, t in gaps) <= out["window_s"] - out["busy_s"] + 1e-9
