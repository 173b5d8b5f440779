"""A configuration, a mix, a cell and a metric are files found by name:
adding one adds files and entries and edits none."""
import copy
import json
import shutil
from pathlib import Path
from types import SimpleNamespace as NS

from bench import harness

ROOT = Path(__file__).resolve().parents[2]


def test_added_files_are_found_without_editing_any(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = copy.deepcopy(harness.load_benchmark())

    d = json.loads((root / "configs" / "stablelm-1.6b.json").read_text())
    d.update(name="stablelm-1.6b-12l", num_hidden_layers=12,
             reduced=["num_hidden_layers"])
    d["overrides"]["n_layers"] = {"value": 12, "why": "a test"}
    (root / "configs" / "stablelm-1.6b-12l.json").write_text(json.dumps(d))
    mix = json.loads((root / "traffic" / "chat.json").read_text())
    mix.update(name="tiny", prompt=dict(mix["prompt"], max=256,
                                        round_up_to=[256]))
    (root / "traffic" / "tiny.json").write_text(json.dumps(mix))
    cell = json.loads((root / "cells" / "stablelm-1.6b.chat.kv-hbm.json")
                      .read_text())
    cell["serving"].update(max_context=512, num_blocks=256,
                           fast_block_budget=32)
    (root / "cells" / "stablelm-1.6b-12l.tiny.json").write_text(
        json.dumps(cell))
    (root / "metrics" / "tokens_sent.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    bench["workloads"].append({"name": "stablelm-1.6b-12l.tiny",
                               "config": "stablelm-1.6b-12l",
                               "traffic": "tiny", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({"name": "tokens_sent", "unit": "requests",
                               "better": "higher",
                               "source": "host_clock", "layer": "engine",
                               "moves": "output_tok_s",
                               "workloads": ["stablelm-1.6b-12l.tiny"]})

    c = harness.Cell.load(bench, "stablelm-1.6b-12l.tiny", root=root)
    assert c.arch.n_layers == 12 and c.mix.prompt_lengths == [256]
    assert c.arch.program_config().n_layers == 12
    assert [m["name"] for m in harness.metrics_for(
        bench, "stablelm-1.6b-12l.tiny", trace=True)] == ["tokens_sent"]
    reader = harness.load_reader("tokens_sent", root=root)
    assert reader.read(NS(records=[1, 2, 3])) == 3
    assert {p: p.read_bytes() for p in before} == before


def test_every_named_file_exists():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        c = harness.Cell.load(bench, w["name"])
        c.arch.program_config()
        harness.serving_config(c)
        for trace in (False, True):
            for m in harness.metrics_for(bench, w["name"], trace):
                assert callable(harness.load_reader(m["name"]).read)
