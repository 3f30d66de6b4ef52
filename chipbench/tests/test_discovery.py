"""A later change adds a mix, a configuration or a per-layer metric by
adding files and entries: the harness finds them by name, and no file that
is already there changes."""
import shutil

import pytest

from chipbench import run as R


@pytest.fixture
def copy_of_chipbench(tmp_path, monkeypatch):
    root = tmp_path / "checkout"
    shutil.copytree(R.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    monkeypatch.setattr(R, "HERE", root / "chipbench")
    yield root / "chipbench"
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} changed"


def test_new_mix_and_metric_found_by_name(copy_of_chipbench, fresh_bench,
                                          cpu):
    here = copy_of_chipbench
    (here / "mixes" / "save5.json").write_text(
        '{"kind": "periods", "log": true, "save_every": 5, '
        '"checkpoint_every": 10, "bg_flush_pages": 32, '
        '"pool_pages_per_record": 2, "first_steps": 5, "period_steps": 10}')
    (here / "layer_metrics" / "saves_per_period.py").write_text(
        "def read(run):\n"
        "    t = run.spans.get('wal.save')\n"
        "    return float(len(t)) if t else None\n")
    cell = {"name": "tiny_whisper.save5", "config": "tiny_whisper",
            "traffic": "save5", "chips": 1}
    fresh_bench["workloads"].append(cell)
    fresh_bench["per_layer"].append(
        {"name": "saves_per_period", "unit": "saves", "better": "lower",
         "source": "program_span", "layer": "save", "moves":
         "train_tokens_per_s", "workloads": ["tiny_whisper.save5"]})
    fresh_bench["end_to_end"][0]["workloads"].append("tiny_whisper.save5")
    _, _, mix = R.find_cell(fresh_bench, "tiny_whisper.save5")
    assert mix["save_every"] == 5
    chosen = [m["name"] for m in R.cell_metrics(fresh_bench, cell, True)]
    assert "saves_per_period" in chosen
    out = R.run_cell(fresh_bench, "tiny_whisper.save5", 9, 0.0, False,
                     chips=cpu)
    assert out["correct"], out["checks"]
    assert out["counts"]["steps"] == 10
    assert "train_tokens_per_s" in out["metrics"]


def test_metric_reader_that_finds_nothing_is_left_out(copy_of_chipbench):
    (copy_of_chipbench / "layer_metrics" / "nothing_here.py").write_text(
        "def read(run):\n    return None\n")
    assert R.read_layer_metric("nothing_here", object()) is None


def test_unknown_workload_is_refused(fresh_bench):
    with pytest.raises(SystemExit):
        R.find_cell(fresh_bench, "no_such.cell")
