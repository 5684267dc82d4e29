"""The load generator: plans are pure functions of the seed, and seeds
change the order of the work, never its amount."""
import collections

import loadgen

ARITY = {"add": 2, "mul": 2, "relu": 1}
OPEN = {"arrivals": {"kind": "stratified_poisson", "rate": 1.5},
        "clients": 64, "ops": {"add": 1, "mul": 1, "relu": 1}}
CLOSED = {"arrivals": {"kind": "closed", "think_s": 0.0}, "clients": 16,
          "pool_per_client": 24, "ops": {"add": 1, "mul": 1, "relu": 1}}


def test_same_seed_same_plan():
    a = loadgen.make_plan(OPEN, ARITY, 16, 2**31 + 5, 30)
    b = loadgen.make_plan(OPEN, ARITY, 16, 2**31 + 5, 30)
    assert [(r.op, r.args, r.due) for r in a.requests] == \
        [(r.op, r.args, r.due) for r in b.requests]


def test_open_seeds_share_the_work():
    plans = [loadgen.make_plan(OPEN, ARITY, 16, s, 30)
             for s in (1, 2**33 + 1)]
    gaps = [sorted(round(y - x, 9) for x, y in
                   zip([0] + [r.due for r in p.requests],
                       [r.due for r in p.requests])) for p in plans]
    assert len(plans[0].requests) == 45
    assert gaps[0] == gaps[1]
    assert collections.Counter(r.op for r in plans[0].requests) == \
        collections.Counter(r.op for r in plans[1].requests) == \
        {"add": 15, "mul": 15, "relu": 15}
    assert [r.due for r in plans[0].requests] != \
        [r.due for r in plans[1].requests]
    assert all(0 < r.due < 30 for r in plans[0].requests)
    assert all(0 <= a < 2**16 for r in plans[0].requests for a in r.args)


def test_closed_plan_gives_every_client_the_same_mix():
    p = loadgen.make_plan(CLOSED, ARITY, 24, 3, 30)
    assert not p.open_loop and p.clients == 16
    for c in range(p.clients):
        assert collections.Counter(r.op for r in p.requests
                                   if r.client == c) == \
            {"add": 8, "mul": 8, "relu": 8}
    assert all(len(r.args) == ARITY[r.op] for r in p.requests)


def test_poisson_copy_matches_the_program():
    from repro.sim import arrivals
    assert loadgen.Poisson(2.0).schedule(20, 7) == \
        arrivals.Poisson(2.0).schedule(20, 7)


def test_every_prefix_holds_the_mix():
    """A window that sees only a client's first requests sees the same mix
    whatever the seed: each prefix is within one of its share."""
    for seed in (5, 2**31 + 9, 2**33 + 1):
        p = loadgen.make_plan(CLOSED, ARITY, 16, seed, 30)
        ops = [r.op for r in p.requests if r.client == 0]
        for k in range(1, len(ops) + 1):
            counts = collections.Counter(ops[:k])
            assert max(counts.values()) - min(
                counts.get(o, 0) for o in ARITY) <= 1
