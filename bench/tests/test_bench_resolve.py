"""The harness finds a cell's configuration, traffic mix and per-layer
metrics by name from files alone, and ``BENCHMARK.json`` keeps to the
shape its readers rely on. CPU only."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_new_cell_mix_and_metric_are_files_and_an_entry(tmp_path, bench):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    mix = json.loads((BENCH / "traffic" / "chat-burst.json").read_text())
    (tmp_path / "traffic" / "long-tail.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "lanes_seen.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "falcon-mamba-7b.long-tail",
                           "config": "falcon-mamba-7b",
                           "traffic": "long-tail", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "lanes_seen", "unit": "1",
                           "better": "higher", "source": "program_counter",
                           "layer": "device", "moves": "itl_p95_ms",
                           "workloads": ["falcon-mamba-7b.long-tail"]})
    spec = harness.resolve(b, "falcon-mamba-7b.long-tail", ROOT,
                           tmp_path / "traffic")
    assert spec["config"]["name"] == "falcon-mamba-7b"
    assert spec["traffic"] == mix
    assert [m["name"] for m in spec["per_layer"]] == ["lanes_seen"]
    assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s",
                                                        "itl_p95_ms"}
    read = harness.reader("lanes_seen", tmp_path / "metrics")
    assert read(harness.Run(steps={1: 0, 2: 0})) == 2.0


def test_unknown_cell_is_an_error(bench):
    with pytest.raises(KeyError):
        harness.resolve(bench, "no-such.cell", ROOT)


def test_every_cell_resolves_with_its_metrics(bench):
    for cell in bench["workloads"]:
        spec = harness.resolve(bench, cell["name"], ROOT)
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            assert m["moves"] in e2e
            assert callable(harness.reader(m["name"]))
        assert spec["config"]["serve"]["prefix_cache"] in (True, False)


def test_benchmark_file_shape(bench):
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        f = ROOT / c["file"]
        assert f.is_file() and f.parts[len(ROOT.parts)] == "bench"
        cfg = json.loads(f.read_text())
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] == c["source"]
        assert {"assumed", "deployment", "serve", "correct"} <= cfg.keys()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4)


def test_cell_file_lays_its_serve_block_over_the_configuration(bench):
    long = harness.resolve(bench, "qwen2.5-14b-tp4.long-decode", ROOT)
    chat = harness.resolve(bench, "qwen2.5-14b.chat-prefix", ROOT)
    assert long["cell"]["chips"] == 4
    assert long["config"]["serve"] == {
        "lanes": 4, "max_len": 9216, "page_size": 16, "segment": 4,
        "buckets": [5120, 5632, 6144], "prefix_cache": False}
    assert chat["config"]["serve"]["max_len"] == 2048
    assert chat["config"]["serve"]["prefix_cache"] is True
    assert long["config"]["correct"] == chat["config"]["correct"]
    assert long["family"] is chat["family"]
    assert long["traffic"]["loop"] == "closed"
    assert {m["name"] for m in long["end_to_end"]} == {
        "itl_p95_ms", "tokens_per_s", "itl_p95_ms.mesh", "tokens_per_s.mesh",
        "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in chat["end_to_end"]} == {
        "ttft_p90_ms", "itl_p95_ms", "tokens_per_s", "peak_hbm_gib",
        "setup_s"}
    assert {m["name"] for m in long["per_layer"]} == {
        "decode_step_ms", "decode_mfu", "gemv_roofline",
        "paged_decode_roofline", "idle_share", "collective_share"}


def test_four_chip_cell_closed_mix_and_cell_block_are_files(tmp_path,
                                                            bench):
    """A cell on four chips with a closed mix and its own serve block is
    new files and an entry."""
    for d in ("traffic", "cells"):
        (tmp_path / d).mkdir()
    mix = {"name": "agents", "loop": "closed", "clients": 8,
           "preload": False, "shape_seed": 3,
           "classes": [{"share": 1.0, "prompt": {"uniform": [100, 200]},
                        "output": {"uniform": [50, 60]}}]}
    (tmp_path / "traffic" / "agents.json").write_text(json.dumps(mix))
    (tmp_path / "cells" / "qwen2.5-14b.agents.json").write_text(
        json.dumps({"serve": {"lanes": 8, "buckets": [128, 256],
                              "prefix_cache": False}}))
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "qwen2.5-14b.agents",
                           "config": "qwen2.5-14b", "traffic": "agents",
                           "chips": 4, "why": "t"})
    spec = harness.resolve(b, "qwen2.5-14b.agents", ROOT,
                           tmp_path / "traffic", tmp_path / "cells")
    serve = spec["config"]["serve"]
    assert serve["lanes"] == 8 and serve["max_len"] == 2048
    assert serve["buckets"] == [128, 256] and serve["prefix_cache"] is False
    assert spec["config"]["correct"]["max_logit_gap"] == 0.2
    assert spec["traffic"] == mix and spec["cell"]["chips"] == 4


def test_cell_file_holds_only_a_serve_block(tmp_path, bench):
    (tmp_path / "qwen2.5-14b.chat-prefix.json").write_text(
        json.dumps({"correct": {"max_logit_gap": 0.25}}))
    with pytest.raises(ValueError, match="only"):
        harness.resolve(bench, "qwen2.5-14b.chat-prefix", ROOT,
                        cells_dir=tmp_path)


def test_cell_files_name_cells_and_chips_are_few(bench):
    names = {w["name"] for w in bench["workloads"]}
    for f in (BENCH / "cells").glob("*.json"):
        assert f.stem in names, f
        assert set(json.loads(f.read_text())) <= {"why", "serve"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert 1 <= len(four) <= max(1, len(bench["workloads"]) // 2)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", [])) <= names
