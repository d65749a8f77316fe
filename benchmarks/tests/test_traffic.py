"""Every seed offers the same work, in another order."""
import json
import os

import pytest

from benchmarks.harness.manifest import BENCH_DIR, load_plugin

SEEDS = [0, 1, 12345, 2 ** 31 + 7, 3_000_000_019]


def _traffic(name):
    with open(os.path.join(BENCH_DIR, "traffic", name + ".json")) as f:
        t = json.load(f)
    return load_plugin("generators", t["generator"]), t["params"]


def test_open_loop_offers_the_same_requests_under_every_seed():
    gen, params = _traffic("chat_steady")
    plans = [gen.generate(params, s, 30.0, 32768) for s in SEEDS]
    first = plans[0]
    n = round(params["rate_per_s"] * 30.0)
    for plan in plans:
        measured = [r for r in plan["requests"] if r["measured"]]
        assert len(measured) == n
        assert plan["offered"] == first["offered"]
        assert sorted(len(r["tokens"]) for r in measured) == \
            sorted(len(r["tokens"]) for r in first["requests"] if r["measured"])
        assert sorted(r["max_tokens"] for r in measured) == \
            sorted(r["max_tokens"] for r in first["requests"] if r["measured"])
        dues = [r["due"] for r in measured]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 30.0
        ref = [r["due"] for r in first["requests"] if r["measured"]]

        every = gen._exponential_gaps(n, 30.0)

        def gaps(ds):  # each request's gap, the first one's from the shifted origin
            return sorted([ds[0] + 0.5 * min(every)]
                          + [b - a for a, b in zip(ds, ds[1:])])

        assert gaps(dues) == pytest.approx(sorted(every), abs=1e-9)
        assert gaps(ref) == pytest.approx(sorted(every), abs=1e-9)
        ramp = [r for r in plan["requests"] if not r["measured"]]
        assert len(ramp) == round(params["rate_per_s"] * params["ramp_seconds"])
        assert all(r["due"] < 0 for r in ramp)
    # the traffic file fixes the schedule ("order_seed"): one order for every
    # seed, other token ids
    orders = {tuple(len(r["tokens"]) for r in p["requests"]) for p in plans}
    assert len(orders) == 1
    assert len({tuple(p["requests"][0]["tokens"]) for p in plans}) == len(SEEDS)
    again = gen.generate(params, SEEDS[3], 30.0, 32768)
    assert again["requests"] == plans[3]["requests"]  # the same seed, the same inputs


def test_open_loop_lengths_respect_their_clips():
    gen, params = _traffic("chat_steady")
    plan = gen.generate(params, 5, 30.0, 32768)
    for r in plan["requests"]:
        assert params["prompt"]["min"] <= len(r["tokens"]) <= params["prompt"]["max"]
        assert params["output"]["min"] <= r["max_tokens"] <= params["output"]["max"]
        assert all(1 <= t < 32768 for t in r["tokens"])


def test_closed_loop_cycles_the_same_lengths_under_every_seed():
    gen, params = _traffic("docs_batch")
    cycles = []
    for s in SEEDS:
        plan = gen.generate(params, s, 30.0, 32768)
        reqs = [plan["next_request"](i) for i in range(params["cycle"])]
        assert all(r["max_tokens"] == params["output_tokens"] for r in reqs)
        cycles.append([len(r["tokens"]) for r in reqs])
        assert plan["callers"] == params["callers"]
        # the second time round the cycle the lengths repeat, the ids do not
        assert len(plan["next_request"](params["cycle"])["tokens"]) == cycles[-1][0]
        assert plan["next_request"](params["cycle"])["tokens"] != reqs[0]["tokens"]
    assert all(sorted(c) == sorted(cycles[0]) for c in cycles)
    assert len({tuple(c) for c in cycles}) == len(SEEDS)
    assert min(cycles[0]) >= 1024 and max(cycles[0]) <= 2048


def test_callers_fit_the_page_pool():
    """C is the number of such requests the pool holds at once."""
    _gen, params = _traffic("docs_batch")
    with open(os.path.join(BENCH_DIR, "configs", "mistral-7b-v0.3-serve.json")) as f:
        dep = json.load(f)["deployment"]
    per_request = -(-(params["prompt"]["max"] + params["output_tokens"]) // dep["page_size"])
    assert params["callers"] == (dep["total_pages"] - 1) // per_request
    assert params["callers"] <= dep["num_slots"]


@pytest.mark.parametrize("seed", SEEDS)
def test_token_dataset_is_seeded(seed):
    gen, params = _traffic("pretrain_4k")
    small = {**params, "rows": 8, "seq": 64}
    a = gen.generate(small, seed, 1.0, 32768)
    b = gen.generate(small, seed, 1.0, 32768)
    assert (a["tokens"] == b["tokens"]).all()
    assert a["tokens"].shape == (8, 64)
    assert (a["tokens"][:, 1:] == a["targets"][:, :-1]).all()
