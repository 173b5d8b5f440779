"""A configuration, a family, a mix, a cell and a metric are files found
by name: adding one adds files and entries and edits none."""
import copy
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import flops, harness, lookup

ROOT = Path(__file__).resolve().parents[2]

# A family of its own: the dense one, counting what the harness asks of
# it, with one weight rule of its own (the V bias, four times as wide).
TOY = '''
import collections
from pathlib import Path

import jax

from bench.lookup import load_family

dense = load_family("dense", Path(__file__).parents[1])
CALLS = collections.Counter()
Q_BLOCK = dense.Q_BLOCK


class Arch(dense.Arch):
    """The dense arch, as this family's own."""


def load(path):
    CALLS["load"] += 1
    return Arch.load(path)


def fill(path, leaf, key):
    CALLS["fill"] += 1
    if str(getattr(path[-1], "key", "")) != "bv":
        return None
    CALLS["own rule"] += 1
    return 0.8 * jax.random.normal(key, leaf.shape)


def logits(arch, *a, **k):
    CALLS["logits"] += 1
    return dense.logits(arch, *a, **k)


def prefill_flops(arch, n):
    CALLS["prefill_flops"] += 1
    return dense.prefill_flops(arch, n)


def decode_flops(arch, cached):
    CALLS["decode_flops"] += 1
    return dense.decode_flops(arch, cached)


def decode_attn_work(arch, lengths):
    CALLS["decode_attn_work"] += 1
    return dense.decode_attn_work(arch, lengths)
'''


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


def test_a_family_is_files_and_entries_alone(tmp_path):
    """A configuration that names a family of its own, with a mix and a
    cell, runs and is checked through that family alone."""
    root = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    bench = copy.deepcopy(harness.load_benchmark())

    (root / "families" / "toy.py").write_text(TOY)
    d = json.loads((root / "configs" / "codeqwen1.5-7b.json").read_text())
    sizes = dict(d_model=64, n_heads=4, n_kv=2, head_dim=16, d_ff=128,
                 vocab=512, n_layers=2)
    d.update(name="toy-2l", family="toy", hidden_size=64,
             num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, vocab_size=512, num_hidden_layers=2,
             overrides={k: {"value": v, "why": "a test"}
                        for k, v in sizes.items()})
    (root / "configs" / "toy-2l.json").write_text(json.dumps(d))
    mix = json.loads((root / "traffic" / "code.json").read_text())
    mix.update(name="tiny",
               prompt=dict(mix["prompt"], median=40, min=16, max=64,
                           round_up_to=[32, 64]),
               output=dict(mix["output"], median=8, min=4, max=16))
    (root / "traffic" / "tiny.json").write_text(json.dumps(mix))
    cell = json.loads((root / "cells" / "codeqwen1.5-7b.code.kv-host.json")
                      .read_text())
    cell["serving"].update(max_context=80, num_blocks=40,
                           fast_block_budget=5)
    cell["check"] = {"max_requests": 16, "limits": {"max_logit_gap": 0.05}}
    (root / "cells" / "toy-2l.tiny.json").write_text(json.dumps(cell))
    bench["workloads"].append({"name": "toy-2l.tiny", "config": "toy-2l",
                               "traffic": "tiny", "chips": 1,
                               "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in ("mfu", "decode_attn_roofline"):
            m["workloads"].append("toy-2l.tiny")

    c = harness.Cell.load(bench, "toy-2l.tiny", root=root)
    toy = lookup.load_family("toy", root)
    assert type(c.arch) is toy.Arch and lookup.family_of(c.arch) is toy
    out = harness.run_cell(bench, "toy-2l.tiny", 2 ** 31 + 3, 2.0, True,
                           time.perf_counter(), allow_cpu=True, cell=c,
                           log=lambda *_: None,
                           trace_dir=tmp_path / "trace")
    assert out["correct"] is True and "mfu" in out["metrics"]
    # the roofline's reader counts attention only where a device ran
    # the kernel: the CPU has none, so the count is asked here
    assert flops.decode_attn_work(c.arch, [5, 0]) == \
        lookup.load_family("dense").decode_attn_work(c.arch, [5, 0])
    assert set(toy.CALLS) == {"load", "fill", "own rule", "logits",
                              "prefill_flops", "decode_flops",
                              "decode_attn_work"}
    assert {p: p.read_bytes() for p in before} == before


def test_an_unknown_family_is_refused_by_its_path(tmp_path):
    d = json.loads((ROOT / "bench" / "configs" / "stablelm-1.6b.json")
                   .read_text())
    d["family"] = "no-such-family"
    path = tmp_path / "x.json"
    path.write_text(json.dumps(d))
    with pytest.raises(harness.BenchError,
                       match=r"bench/families/no-such-family\.py"):
        lookup.load_arch(path)
