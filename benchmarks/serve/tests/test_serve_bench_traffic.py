"""The traffic generator: seeded, bucketed, the same work for every seed,
and a new mix found by name with no edit to the harness."""
import dataclasses
import itertools
import json
import shutil

import numpy as np
import pytest

from serve_bench_tiny import SERVE
import catalog
import traffic

REAL_MIXES = ("chat_backlog", "code_open")


def _take(mix, seed, n, vocab=151936):
    return list(itertools.islice(traffic.stream(mix, seed, vocab), n))


def _load(name, rate=1.2):
    return traffic.load(catalog.traffic_file(name), name, rate)


def test_a_seed_gives_the_same_schedule():
    for name in REAL_MIXES:
        mix = _load(name)
        a, b = _take(mix, 2**40 + 17, 45), _take(mix, 2**40 + 17, 45)
        for x, y in zip(a, b):
            assert x.due_s == y.due_s
            assert x.max_new_tokens == y.max_new_tokens
            assert np.array_equal(x.prompt, y.prompt)


def test_each_configuration_has_its_own_rate():
    """An open-loop cell's rate is its own file's, not the mix's."""
    bench = catalog.load_benchmark()
    for w in bench["workloads"]:
        cell = catalog.find_cell(w["name"])
        mix = catalog.mix(cell)
        if mix.arrival == "poisson":
            assert mix.rate_per_s == cell.offered["rate_per_s"] > 0
            assert "rate_per_s" not in json.loads(
                catalog.traffic_file(cell.traffic).read_text())["arrival"]
    with pytest.raises(ValueError):
        traffic.load(catalog.traffic_file("code_open"), "code_open")


def test_seeds_that_differ_in_high_bits_differ():
    mix = _load("code_open")
    a, b = _take(mix, 5, 3), _take(mix, 2**33 + 5, 3)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_come_only_from_the_buckets():
    for name in REAL_MIXES:
        mix = _load(name)
        reqs = _take(mix, 3, 200, vocab=1000)
        lengths = {len(r.prompt) for r in reqs}
        assert lengths <= set(mix.prompt_lengths)
        assert all(0 <= r.prompt.min() and r.prompt.max() < 1000
                   for r in reqs)
        assert all(mix.out_min <= r.max_new_tokens <= mix.out_max
                   for r in reqs)


def test_every_seed_offers_the_same_work_per_block():
    for name in REAL_MIXES:
        mix = _load(name)
        blocks = []
        for seed in (1, 2, 3**30):
            reqs = _take(mix, seed, mix.block * 2)
            blocks.append((
                sorted(len(r.prompt) for r in reqs),
                sorted(r.max_new_tokens for r in reqs),
                [round(r.due_s, 9) for r in reqs]))
        assert blocks[0] == blocks[1] == blocks[2]


def test_bucket_counts_follow_the_weights():
    mix = _load("chat_backlog")
    assert traffic.bucket_counts(mix) == [2, 3, 6, 5]
    code = _load("code_open")
    assert traffic.bucket_counts(code) == [3, 5, 2]
    # the medians the mixes cite sit in the middle buckets
    assert sorted(traffic.block_prompt_lengths(mix))[mix.block // 2] == 1024
    assert sorted(traffic.block_prompt_lengths(code))[code.block // 2] == 1536


def test_each_period_holds_its_count_at_poisson_times():
    code = _load("code_open", rate=1.5)
    span = code.period / code.rate_per_s
    due = np.asarray(list(itertools.islice(
        traffic.arrivals(code, 9), code.period * 40)))
    assert np.all(np.diff(due) >= 0)
    per_period = np.bincount((due // span).astype(int))
    assert list(per_period) == [code.period] * 40
    # gaps of a Poisson process: exponential, mean 1/rate, sd as large
    gaps = np.diff(due)
    assert abs(gaps.mean() * code.rate_per_s - 1) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1) < 0.1


def test_a_fixed_path_is_shared_and_a_seeded_path_is_not():
    code = _load("code_open")
    assert code.path_seed is not None

    def path(mix, seed):
        return list(itertools.islice(traffic.arrivals(mix, seed), 30))

    assert path(code, 1) == path(code, 2**33 + 1)
    seeded = dataclasses.replace(code, path_seed=None)
    assert path(seeded, 1) == path(seeded, 1)
    assert path(seeded, 1) != path(seeded, 2**33 + 1)


def test_a_new_mix_and_metric_are_found_by_name(tmp_path):
    """Adding a mix, a cell and a metric adds files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(SERVE, root / catalog.SUBDIR,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    bench = catalog.load_benchmark()
    mix_file = root / catalog.SUBDIR / "traffic" / "burst_test.json"
    mix_file.write_text(json.dumps({
        "arrival": {"kind": "poisson", "period": 20, "path_seed": 4},
        "prompt_buckets": [[256, 1.0]],
        "output_tokens": {"kind": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 2, "max": 16},
        "block": 5}))
    (root / catalog.SUBDIR / "offered" / "qwen3-1.7b.burst_test.json"
     ).write_text(json.dumps({"rate_per_s": 9.0}))
    (root / catalog.SUBDIR / "metrics" / "probe_count.burst.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["workloads"].append({"name": "qwen3-1.7b.burst_test",
                               "config": "qwen3-1.7b", "traffic": "burst_test",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "probe_count.burst", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "test", "moves": "itl_p95_ms",
                               "workloads": ["qwen3-1.7b.burst_test"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = catalog.find_cell("qwen3-1.7b.burst_test", root=root)
    mix = catalog.mix(cell, root)
    assert mix.rate_per_s == 9.0
    reqs = _take(mix, 11, 10)
    assert {len(r.prompt) for r in reqs} == {256}
    assert [m["name"] for m in cell.per_layer] == ["probe_count.burst"]
    assert catalog.metric_reader("probe_count.burst", root).read(None) == 42.0
    # every file the harness already had is untouched
    for path in SERVE.rglob("*"):
        if path.is_file() and ".cache" not in path.parts \
                and "__pycache__" not in path.parts:
            copy = root / catalog.SUBDIR / path.relative_to(SERVE)
            assert copy.read_bytes() == path.read_bytes()


def test_a_metric_reader_is_shared_by_the_name_less_its_last_part(tmp_path):
    """``mfu.chat`` and ``mfu.code`` read with ``metrics/mfu.py``; a file
    of the metric's full name, added later, takes its place."""
    assert catalog.metric_reader("mfu.chat").__file__ == \
        catalog.metric_reader("mfu.code").__file__
    assert catalog.metric_reader("mfu.any_new_mix").__file__.endswith(
        "metrics/mfu.py")
    root = tmp_path / "checkout"
    shutil.copytree(SERVE, root / catalog.SUBDIR,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / catalog.SUBDIR / "metrics" / "mfu.train.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert catalog.metric_reader("mfu.train", root).read(None) == 7.0
    bench = catalog.load_benchmark()
    shared = {m["name"].rsplit(".", 1)[0] for m in bench["per_layer"]}
    assert {p.stem for p in (SERVE / "metrics").glob("*.py")} == shared
