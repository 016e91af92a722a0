"""A new mix, configuration, cell and metric need new files and entries
only: the harness finds them by name."""
import json
import os
import shutil

import pytest

from harness.spec import load_cell, metric_reader, peaks_for
from harness.traffic import Limits, Mix

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture()
def checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path


def add_throwaway(root):
    """A mix, a cell and a metric, added as files and entries alone."""
    (root / "bench" / "traffic" / "short_chat.json").write_text(json.dumps({
        "loop": "open", "system_prompt": 0,
        "prompt": {"length": {"dist": "uniform", "min": 4, "max": 12}},
        "output": {"length": {"dist": "fixed", "value": 6}},
        "guide": None}))
    cell = json.loads((root / "bench" / "cells" /
                       "qwen2-1.5b.rag.json").read_text())
    cell["load"] = {"rate": 2.0}
    (root / "bench" / "cells" / "qwen2-1.5b.short_chat.json").write_text(
        json.dumps(cell))
    (root / "bench" / "metrics" / "requests_per_step.py").write_text(
        "def read(run):\n"
        "    c = run.counters\n"
        "    return c['admitted'] / c['decode_steps'] "
        "if c['decode_steps'] else None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "qwen2-1.5b.short_chat", "config": "qwen2-1.5b",
        "traffic": "short_chat", "chips": 1, "why": "throwaway"})
    bench["per_layer"].append({
        "name": "requests_per_step", "unit": "1/step", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tpot_p50_ms", "workloads": ["qwen2-1.5b.short_chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_cell_found_by_files_alone(checkout):
    add_throwaway(checkout)
    cell = load_cell("qwen2-1.5b.short_chat", root=checkout)
    assert cell.traffic["name"] == "short_chat"
    assert cell.config["hidden_size"] == 1536
    assert cell.settings["load"] == {"rate": 2.0}
    names = [m["name"] for m in cell.per_layer]
    assert "requests_per_step" in names
    assert "prefix_hit_pct" not in names          # rag's alone
    e2e = [m["name"] for m in cell.end_to_end]
    assert "tpot_p50_ms" in e2e and "setup_s" in e2e
    read = metric_reader("requests_per_step", root=checkout)

    class Run:
        counters = {"admitted": 6.0, "decode_steps": 3.0}
    assert read(Run()) == 2.0
    mix = Mix(cell.traffic, 5, 1000, None, Limits(64, 128, 33))
    reqs = mix.open_loop(2.0, 10.0)
    assert len(reqs) == 20
    assert all(4 <= len(r.prompt) <= 12 and r.max_new == 6 for r in reqs)


def test_unknown_names_are_errors(checkout):
    with pytest.raises(KeyError):
        load_cell("no-such.cell", root=checkout)
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary", root=checkout)
    assert peaks_for("TPU v5 lite", root=checkout)["bf16_flops"] == 197e12


def test_every_listed_metric_has_a_reader():
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    for m in bench["per_layer"]:
        assert callable(metric_reader(m["name"]))
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end


def add_family(root):
    """A model family that exists only as a new file (the decoder's source
    under another name, with three layers in its rehearsal), a
    configuration that names it, and a cell on it."""
    src = (root / "bench" / "families" / "decoder.py").read_text()
    src = src.replace('"num_hidden_layers": 2,', '"num_hidden_layers": 3,')
    (root / "bench" / "families" / "decoder3.py").write_text(src)
    cfg = json.loads((root / "bench" / "configs" /
                      "qwen2-1.5b.json").read_text())
    cfg["family"] = "decoder3"
    (root / "bench" / "configs" / "qwen2-1.5b-d3.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "cells" / "qwen2-1.5b-d3.rag.json").write_text(
        (root / "bench" / "cells" / "qwen2-1.5b.rag.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0], name="qwen2-1.5b-d3",
                                 file="bench/configs/qwen2-1.5b-d3.json"))
    bench["workloads"].append({
        "name": "qwen2-1.5b-d3.rag", "config": "qwen2-1.5b-d3",
        "traffic": "rag", "chips": 1, "why": "throwaway"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_new_family_found_by_files_alone(checkout):
    """A configuration whose family lives in a new file loads through
    ``load_cell`` and finishes a CPU rehearsal through the family."""
    import subprocess
    import sys

    from harness.spec import family_module
    add_family(checkout)
    os.symlink(os.path.join(ROOT, "src"), checkout / "src")
    cell = load_cell("qwen2-1.5b-d3.rag", root=checkout)
    fam = family_module(cell.config, root=checkout)
    assert fam.shape_of(cell.config, rehearsal=True)["layers"] == 3
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b-d3.rag",
         "--seed", "4000000023", "--seconds", "3", "--trace", "0",
         "--cpu-rehearsal"], cwd=checkout, env=env, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(x for x in p.stderr.splitlines() if "family decoder3 {" in x)
    assert json.loads(line.split("family decoder3 ", 1)[1])["layers"] == 3
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_replicas_must_match_the_chips(checkout):
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    fleet = [w for w in bench["workloads"]
             if w["name"] == "qwen2-1.5b.fleet4.rag"]
    assert fleet and fleet[0]["chips"] == 4
    assert load_cell("qwen2-1.5b.fleet4.rag", root=checkout).settings[
        "replicas"]["n"] == 4
    fleet[0]["chips"] = 1
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError):
        load_cell("qwen2-1.5b.fleet4.rag", root=checkout)
