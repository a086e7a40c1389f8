"""A configuration, a traffic mix, a check and a metric added as new files
plus new BENCHMARK.json entries are found by name: no existing file of the
harness changes."""
import json
import shutil

from bench import run as R
from bench import traffic


def test_new_files_are_found_by_name(tmp_path):
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    b = tmp_path / "bench"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    bj = json.loads((R.ROOT / "BENCHMARK.json").read_text())

    spec = json.loads((b / "configs" / "qwen2-1.5b-lcd4.json").read_text())
    spec["name"] = "dummy-cfg"
    (b / "configs" / "dummy-cfg.json").write_text(json.dumps(spec))
    mix = traffic.load("chat", b)
    mix["rate_per_s"] = 0.123
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (b / "checks" / "dummy-cfg.dummy-mix.json").write_text(
        json.dumps({"limits": {"max_gap": {"limit": 0.5}}}))
    (b / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bj["configs"].append({"name": "dummy-cfg", "source": "x",
                          "file": "bench/configs/dummy-cfg.json",
                          "reduced": [], "why": "x"})
    bj["workloads"].append({"name": "dummy-cfg.dummy-mix",
                            "config": "dummy-cfg", "traffic": "dummy-mix",
                            "chips": 1, "why": "x"})
    bj["per_layer"].append({"name": "dummy_metric.new", "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "x", "moves": "ttft_mean_ms",
                            "workloads": ["dummy-cfg.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bj))

    cell = R.load_cell("dummy-cfg.dummy-mix", root=tmp_path)
    assert cell.spec["name"] == "dummy-cfg"
    assert cell.mix["rate_per_s"] == 0.123
    assert cell.limits["max_gap"]["limit"] == 0.5
    assert [m["name"] for m in cell.per_layer] == ["dummy_metric.new"]
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert R.reader("dummy_metric.new", root=b)(None) == 42.0
    assert R.reader("pad_row_share.anything", root=b) is not None
    after = {p: p.read_bytes() for p in before}
    assert after == before
