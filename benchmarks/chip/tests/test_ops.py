"""The algorithm's operations and bytes, pinned to hand arithmetic."""
import ops
from repro.core.params import TEST_PARAMS_4BIT, TEST_PARAMS_6BIT
from repro.launch.roofline import pbs_round_model


def test_test4bit_by_hand():
    p = TEST_PARAMS_4BIT              # n=96 N=2048 k=1 pbs 2 ks 5
    # M = 1024: 5*1024*10 + 6*1024 = 57344 per transform
    assert ops.transform_ops(2048) == 57344
    # 4 forward, 2 inverse, MAC 8*2*2*2*1024, rotate+add 2*2*2048
    assert ops.cmux_ops(p) == 4 * 57344 + 2 * 57344 + 65536 + 8192
    assert ops.keyswitch_ops(p) == 2 * 2048 * 5 * 97
    assert ops.pbs_ops(p) == 1986560 + 97 + 96 * 417792 == 42094689
    assert ops.bsk_bytes(p) == 96 * 2 * 2 * 2 * 1024 * 16 == 12582912
    assert ops.ksk_bytes(p) == 2048 * 5 * 97 * 8 == 7946240
    assert ops.row_bytes(p) == 2 * 2049 * 8 + 2048 * 8


def test_test6bit_by_hand():
    p = TEST_PARAMS_6BIT              # n=128 N=4096 k=1 pbs 2 ks 4
    assert ops.transform_ops(4096) == 5 * 2048 * 11 + 6 * 2048 == 124928
    assert ops.cmux_ops(p) == 6 * 124928 + 8 * 2 * 2 * 2 * 2048 + 16384
    assert ops.keyswitch_ops(p) == 2 * 4096 * 4 * 129
    assert ops.pbs_ops(p) == 4227072 + 129 + 128 * 897024 == 119046273
    assert ops.bsk_bytes(p) == 128 * 2 * 2 * 2 * 2048 * 16


def test_round_least_time_and_key_bytes():
    p = TEST_PARAMS_4BIT
    m = pbs_round_model(p, 64)
    assert ops.bsk_bytes(p) == m.bsk_bytes and ops.ksk_bytes(p) == m.ksk_bytes
    w = ops.RoundWork(rows=64, keyswitched=8)
    assert w.ops(p) == 64 * ops.blind_rotate_ops(p) + 8 * ops.keyswitch_ops(p)
    assert w.bytes(p) == 12582912 + 7946240 + 64 * ops.row_bytes(p)
    # 64 rows at the v5e peaks: the key bytes bound it, about 28.9 us
    t = w.least_seconds(p, 197e12, 819e9)
    assert t == w.bytes(p) / 819e9 and 2.8e-5 < t < 3.0e-5
