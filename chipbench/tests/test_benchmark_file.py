"""``BENCHMARK.json`` keeps to the shape the benchmark's contract states, and
every name in it resolves to a file under ``chipbench/``."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# widths that a cut may never name (besides any key ending in _dim or _rank)
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "d_model", "num_experts_per_tok", "head_dim"}
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    cmd = BENCH["command"]
    assert cmd == ["python3", "chipbench/run.py"] and len(cmd) <= 32
    r = BENCH["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    # a full check of 24 cells fits the driver's 43,200 s
    assert 1200 + (2 + 14 * 24) * (r + 60) + 24 * 2 * 90 <= 43200


def test_configs_resolve_and_state_their_cuts():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        f = ROOT / c["file"]
        assert c["file"].startswith("chipbench/") and f.is_file()
        cfg = json.loads(f.read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert f.with_suffix(".py").is_file()            # its reference
        for k in c["reduced"]:
            assert NAME.match(k) and k in cfg
            assert not k.endswith(("_dim", "_rank")) and k not in WIDTHS, k


def test_cells_metrics_and_layers():
    configs = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert len(cells) == len(BENCH["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(cells)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (ROOT / "chipbench" / "mixes" / f"{w['traffic']}.json").is_file()
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert (ROOT / "chipbench" / "layer_metrics"
                / f"{m['name']}.py").is_file()
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        reported = [n for n, m in e2e.items()
                    if w in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(w in m["workloads"] for m in BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_configuration_has_its_operation_count():
    for c in BENCH["configs"]:
        family = json.loads((ROOT / c["file"]).read_text())["model_type"]
        count = ROOT / "chipbench" / "counts" / f"{family}.py"
        assert count.is_file(), f"{c['name']}: no {count}"
