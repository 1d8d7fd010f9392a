"""The generators: the same multiset for every seed, the same requests
for the same seed, and the same SHAPE of work (buckets, budgets, due
times, in order) whatever the seed."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench.generators import (_multiset as ms, closed_loop,  # noqa: E402
                                  open_loop, token_batches)

SEEDS = (0, 7, 2 ** 31 + 12345)
FULL = {"closed_decode": (closed_loop, 30.0), "open_prefill": (open_loop, 30.0)}


def full(kind, name):
    return tiny._load(kind, name)


def serve_plan(traffic_name, seed):
    gen, seconds = FULL[traffic_name]
    return gen.make(full("traffic", traffic_name),
                    full("configs", "gpt2_medium_d12"), seed, seconds)


def requests_of(plan):
    if "clients" in plan:
        return [r for c in plan["clients"] for r in c]
    return plan["requests"]


@pytest.mark.parametrize("traffic", sorted(FULL))
def test_same_multiset_of_lengths_for_every_seed(traffic):
    sets = [sorted(len(p) for p, _b in requests_of(serve_plan(traffic, s)))
            for s in SEEDS]
    assert sets[0] == sets[1] == sets[2]
    spec = full("traffic", traffic)["prompt_len"]
    assert sets[0][0] >= spec["lo"] and sets[0][-1] <= spec["hi"]


@pytest.mark.parametrize("traffic", sorted(FULL))
def test_same_shape_of_work_in_the_same_order_for_every_seed(traffic):
    buckets = full("configs", "gpt2_medium_d12")["build"]["prompt_buckets"]
    shapes = []
    for s in SEEDS:
        plan = serve_plan(traffic, s)
        shapes.append(([(ms.bucket_of(len(p), buckets), b)
                        for p, b in requests_of(plan)],
                       list(plan.get("due", []))))
    assert shapes[0] == shapes[1] == shapes[2]


@pytest.mark.parametrize("traffic", sorted(FULL))
def test_same_seed_same_requests_other_seed_other_tokens(traffic):
    a, b, c = (requests_of(serve_plan(traffic, s)) for s in (7, 7, 8))
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1]
               for x, y in zip(a, b))
    assert any(len(x[0]) != len(y[0]) or not np.array_equal(x[0], y[0])
               for x, y in zip(a, c))


def test_closed_loop_first_round_is_staggered_and_the_rest_is_whole():
    tr = full("traffic", "closed_decode")
    plan = serve_plan("closed_decode", 3)
    first = sorted(reqs[0][1] for reqs in plan["clients"])
    later = [b for reqs in plan["clients"] for _p, b in reqs[1:]]
    assert min(later) >= tr["max_new"]["lo"] and max(later) <= tr["max_new"]["hi"]
    assert first[0] >= tr["first_round_min"] > tr["prime_decode_steps"] + 1
    # spread over the whole range, not bunched at the shortest budget
    assert first[len(first) // 4] < 48 < first[3 * len(first) // 4]
    assert len(set(first)) > len(first) // 2


def test_closed_loop_traffic_keeps_the_page_pool_about_half_leased():
    """What the cell's ``why`` says of the pool (REVIEW of PR 23: the
    first lengths left 88 % of it empty): every prompt pads to bucket 256
    or 512, half each, a slot holds 256-704 of its 1024 rows, and the
    leases of whole requests cover about half of a slot's pages."""
    build = full("configs", "gpt2_medium_d12")["build"]
    per_slot = build["prompt_len"] + build["max_new"]
    whole = [(ms.bucket_of(len(p), build["prompt_buckets"]), b)
             for reqs in serve_plan("closed_decode", 3)["clients"]
             for p, b in reqs[1:]]
    buckets = [bk for bk, _b in whole]
    assert set(buckets) == {256, 512}
    assert 0.45 < buckets.count(512) / len(buckets) < 0.55
    rows = [bk + b for bk, b in whole]
    assert min(rows) >= 256 + 64 and max(rows) <= 512 + 192 < per_slot
    pages = [-(-r // build["page_size"]) for r in rows]
    # weighted by how long a request holds its lease (its budget)
    held = np.average(pages, weights=[b for _bk, b in whole])
    assert 0.45 < held / (per_slot // build["page_size"]) < 0.6


def test_open_loop_schedule_is_poisson_at_the_files_rate():
    tr = full("traffic", "open_prefill")
    plan = serve_plan("open_prefill", 3)
    n = len(plan["requests"])
    assert n == round(tr["rate_per_s"] * 30.0)
    gaps = np.diff(plan["due"])
    assert abs(plan["due"][-1] + gaps.mean() - n / tr["rate_per_s"]) \
        < 2.0 / tr["rate_per_s"]
    # exponential gaps: standard deviation about the mean
    assert 0.8 < gaps.std() / gaps.mean() < 1.2
    assert len({ms.bucket_of(len(p), [128, 256, 512])
                for p, _ in plan["requests"]}) == 3


@pytest.mark.parametrize("spec,n,lo,hi", [
    ({"dist": "uniform", "lo": 64, "hi": 192}, 100, 64, 192),
    ({"dist": "log_uniform", "lo": 129, "hi": 512}, 100, 129, 512),
    (1, 10, 1, 1)])
def test_quantile_multisets(spec, n, lo, hi):
    q = ms.quantiles(spec, n)
    assert len(q) == n and q.min() >= lo and q.max() <= hi
    assert np.all(np.diff(q) >= 0)
    if isinstance(spec, dict) and spec["dist"] == "log_uniform":
        assert np.median(q) < (lo + hi) / 2      # mass at the short end


def test_exponential_gaps_sum_to_the_window():
    g = ms.exponential_gaps(240, 8.0)
    assert abs(g.sum() - 30.0) < 1e-9 and g.min() > 0


def test_reorder_within_buckets_keeps_the_bucket_sequence():
    lengths = np.asarray([5, 100, 200, 300, 90, 500, 129, 30])
    out = ms.reorder_within_buckets(lengths, [128, 256, 512],
                                    np.random.RandomState(1))
    assert sorted(out) == sorted(lengths)
    assert [ms.bucket_of(int(v), [128, 256, 512]) for v in out] == \
        [ms.bucket_of(int(v), [128, 256, 512]) for v in lengths]


@pytest.mark.parametrize("traffic,chips", [("resident_feed", 1),
                                           ("resident_feed_dp4", 4)])
def test_token_batches_shapes_do_not_depend_on_the_seed(traffic, chips):
    cfg, tr = tiny.train_config(), tiny.train_traffic(traffic)
    a, b, c = (token_batches.make(tr, cfg, s, chips) for s in (1, 1, 2))
    assert a["batch"] == tr["batch_per_chip"] * chips
    shapes = lambda d: [{k: v.shape for k, v in fs.items()}   # noqa: E731
                        for fs in d["feed_sets"]]
    assert shapes(a) == shapes(c) and len(a["feed_sets"]) == tr["feed_sets"]
    assert all(np.array_equal(x[k], y[k]) for x, y in
               zip(a["feed_sets"], b["feed_sets"]) for k in x)
    assert not np.array_equal(a["feed_sets"][0]["src_ids"],
                              c["feed_sets"][0]["src_ids"])
