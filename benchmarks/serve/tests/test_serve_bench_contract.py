"""BENCHMARK.json keeps to the benchmark's contract: allowed keys,
names and units; every name resolves to a file of its own."""
import json
import re
import shutil

import pytest

from serve_bench_tiny import SERVE
import catalog

BENCH_FILE = catalog.ROOT / "BENCHMARK.json"
BENCH = json.loads(BENCH_FILE.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FILE_PART = re.compile(r"^[A-Za-z0-9_.-]+$")
ARCH_FUNCTIONS = ("arch_config", "build", "prefill_flops", "decode_flops",
                  "paged_attention_call")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_entry_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", *KEYS}
    assert BENCH_FILE.stat().st_size <= 64 * 1024
    for section, keys in KEYS.items():
        for entry in BENCH[section]:
            extra = set(entry) - keys
            assert extra <= ({"workloads"} if section in
                             ("end_to_end", "per_layer") else set()), entry


def test_names_units_and_texts():
    names = []
    for section in KEYS:
        for entry in BENCH[section]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            for key in ("why", "layer", "source"):
                if key in entry:
                    assert _text_ok(entry[key]), (entry["name"], key)
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    assert all(_text_ok(word) for word in BENCH["command"])
    assert len(BENCH["command"]) <= 32


def test_sources_bounds_and_run_length():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time
    assert 2 + 14 * 24 * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BENCH["workloads"]}
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(cells) // 2)
    for name in cells:
        cell = catalog.find_cell(name)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m["moves"] in reported, (name, m["name"])
        assert cell.chips in (1, 4)
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells


def test_every_name_resolves_to_its_file():
    for c in BENCH["configs"]:
        path = catalog.ROOT / c["file"]
        assert path.is_file() and path.is_relative_to(SERVE)
        config = json.loads(path.read_text())
        assert config["reduced"] == c["reduced"]
        assert (SERVE / "references" / f"{config['reference']}.py").is_file()
        arch = SERVE / "architectures" / f"{config['architecture']}.py"
        assert arch.is_file()
        module = catalog.architecture(config["architecture"])
        assert all(callable(getattr(module, f)) for f in ARCH_FUNCTIONS)
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    for w in BENCH["workloads"]:
        assert catalog.traffic_file(w["traffic"]).is_file()
        limits = json.loads(
            (SERVE / "limits" / f"{w['name']}.json").read_text())
        assert limits["logit_gap_max"]["limit"] > 0
    for m in BENCH["per_layer"]:
        assert callable(catalog.metric_reader(m["name"]).read)
    assert BENCH["paths"] == [str(catalog.SUBDIR)]


def test_a_configuration_without_an_architecture_names_its_file(tmp_path):
    shutil.copy(BENCH_FILE, tmp_path)
    entry = BENCH["configs"][0]
    config = json.loads((catalog.ROOT / entry["file"]).read_text())
    del config["architecture"]
    path = tmp_path / entry["file"]
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(config))
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == entry["name"])
    with pytest.raises(ValueError, match="architecture") as e:
        catalog.find_cell(cell, root=tmp_path)
    assert str(path) in str(e.value)


@pytest.mark.parametrize("path", sorted(
    p for p in SERVE.rglob("*") if p.is_file()
    and ".cache" not in p.parts and "__pycache__" not in p.parts))
def test_files_are_named_from_name_characters(path):
    assert all(FILE_PART.match(part)
               for part in path.relative_to(SERVE).parts)
