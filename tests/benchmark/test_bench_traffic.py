"""The load generator is a pure function of `--seed` and the cell's parameters."""

import collections
import glob
import json
import os

import pytest

from benchmark.harness import cells, traffic

CELLS = sorted(os.path.basename(p)[:-len(".json")] for p in glob.glob(
    os.path.join(cells.BENCH, "workloads", "*.json")))
GENERATE = [c for c in CELLS
            if cells.load_json("workloads", c)["driver"] == "generate"]

CHAT = {
    "arrival": {"kind": "closed", "clients": 4},
    "prompt_tokens": {"dist": "log_uniform", "low": 32, "high": 256},
    "answer_tokens": {"dist": "uniform", "low": 64, "high": 192},
    "pool": 128,
    "sampling": {"temperature": 1.0, "top_k": 0, "top_p": 0.0},
}


def test_quantile_lengths_cover_the_range_evenly():
    uniform = traffic.quantile_lengths(CHAT["answer_tokens"], 129)
    assert min(uniform) == 64 and max(uniform) == 192
    assert sorted(uniform) == uniform and len(set(uniform)) == 129
    logu = traffic.quantile_lengths(CHAT["prompt_tokens"], 128)
    assert 32 <= min(logu) <= 33 and 250 <= max(logu) <= 256
    # log-uniform: as many lengths under the geometric mean as over it
    assert sum(1 for v in logu if v < (32 * 256) ** 0.5) == 64


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11, 4000000001])
def test_same_seed_same_requests(seed):
    a = traffic.Stream(CHAT, seed, 50272)
    b = traffic.Stream(CHAT, seed, 50272)
    assert [a.request(i) for i in range(40)] == [b.request(i)
                                                 for i in range(40)]
    r = a.request(3)
    assert set(r) == {"prompt_ids", "n_steps", "seed", "temperature",
                      "top_k", "top_p"}
    assert all(1 <= t < 50272 for t in r["prompt_ids"])


def test_every_seed_gets_the_same_sizes_in_another_order():
    n = CHAT["pool"]
    a = traffic.Stream(CHAT, 1, 1000)
    b = traffic.Stream(CHAT, 2, 1000)
    sizes_a = [a.sizes(i) for i in range(n)]
    sizes_b = [b.sizes(i) for i in range(n)]
    assert sizes_a != sizes_b
    assert collections.Counter(sizes_a) == collections.Counter(sizes_b)
    assert collections.Counter(sizes_a) == collections.Counter(
        traffic.size_pool(CHAT))
    # the second cycle is another order of the same pool again
    assert collections.Counter(a.sizes(n + i) for i in range(n)) == \
        collections.Counter(sizes_a)
    assert a.request(0)["prompt_ids"] != b.request(0)["prompt_ids"]


def test_poisson_due_times_same_gaps_every_seed():
    spec = dict(CHAT, arrival={"kind": "poisson", "rate": 4.0}, pool=64)
    a, b = traffic.Stream(spec, 1, 100), traffic.Stream(spec, 2, 100)
    due_a = [a.due(i) for i in range(130)]
    due_b = [b.due(i) for i in range(130)]
    assert due_a == sorted(due_a) and due_a[0] > 0
    assert due_a[:64] != due_b[:64]
    # one cycle of 64 gaps sums to the same time whatever the order, and
    # their mean is close to 1 / rate
    assert due_a[63] == pytest.approx(due_b[63])
    assert due_a[63] / 64 == pytest.approx(0.25, rel=0.05)
    assert due_a[127] == pytest.approx(2 * due_a[63])
    with pytest.raises(ValueError):
        traffic.Stream(CHAT, 1, 100).due(0)


def test_shared_prefix_groups():
    spec = dict(CHAT, sharing={"kind": "prefix", "tokens": 24, "groups": 2})
    s = traffic.Stream(spec, 5, 1000)
    heads = {tuple(s.request(i)["prompt_ids"][:24]) for i in range(20)}
    assert len(heads) == 2
    tails = {tuple(s.request(i)["prompt_ids"][24:]) for i in range(20)}
    assert len(tails) == 20


@pytest.mark.parametrize("cell", GENERATE)
def test_cell_pool_fits_its_slots_and_its_model(cell):
    spec = cells.load_json("workloads", cell)
    sizes = cells.load_json("configs", spec["config"])
    t = spec["traffic"]
    longest = traffic.longest_request(t)
    assert longest <= t["prompt_tokens"]["high"] + t["answer_tokens"]["high"]
    assert longest <= sizes["max_position_embeddings"]
    page = sizes["kv_page_size"]
    pages = traffic.pages_for(t, spec["server"]["decode_slots"], page)
    assert (pages - 1) * page >= spec["server"]["decode_slots"] * longest
    # every prompt has a bucket of its own ladder at or above it
    assert max(spec["server"]["prompt_buckets"]) >= t["prompt_tokens"]["high"]


def test_issue_23_cells_have_the_traffic_the_issue_fixed():
    chat = cells.load_json("workloads", "opt_1_3b.chat_decode")
    assert chat["server"]["decode_slots"] == 32
    assert chat["traffic"]["arrival"]["clients"] == 32
    assert chat["traffic"]["prompt_tokens"] == {
        "dist": "log_uniform", "low": 32, "high": 256}
    assert chat["traffic"]["answer_tokens"] == {
        "dist": "uniform", "low": 64, "high": 192}
    assert chat["server"]["prompt_buckets"] == [64, 256]
    assert traffic.pages_for(chat["traffic"], 32, 64) == 32 * 7 + 1
    long = cells.load_json("workloads", "opt_1_3b.long_prompt")
    assert long["server"]["decode_slots"] == 8
    assert long["traffic"]["prompt_tokens"] == {
        "dist": "uniform", "low": 768, "high": 1792}
    assert long["traffic"]["answer_tokens"] == {
        "dist": "uniform", "low": 16, "high": 48}
    assert long["server"]["prompt_buckets"] == [1024, 2048]
    assert traffic.pages_for(long["traffic"], 8, 64) == 8 * 29 + 1
    for spec in (chat, long):
        assert spec["server"]["prefix_cache"] is False
        assert spec["traffic"]["sampling"] == {
            "temperature": 1.0, "top_k": 0, "top_p": 0.0}
        assert json.dumps(spec["traffic"]["sharing"]) == '{"kind": "none"}'
