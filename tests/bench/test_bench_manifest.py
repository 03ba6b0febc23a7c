"""BENCHMARK.json against the benchmark's contract: names and units in
the allowed characters, every file found by name, each per-layer
metric's `moves` reported in every cell that lists it."""

import json
import pathlib
import re

import pytest

from harness.manifest import Manifest, load_module

ROOT = pathlib.Path(__file__).parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
#: a width: hidden, intermediate, latent, state or projection sizes,
#: head sizes, expansion factors, experts per token (the vocabulary may
#: be the chip's share, so vocab_size is not one)
WIDTH = re.compile(r"((?<!vocab)_size$|_dim$|_rank$|^head_|expan|per_tok)")


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(MAN["command"]) <= 32
    for p in MAN["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    for word in MAN["command"]:
        assert TEXT.match(word)
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in MAN["paths"])


def test_entries_have_exactly_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_text():
    entries = (MAN["configs"] + MAN["workloads"] + MAN["end_to_end"]
               + MAN["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MAN[kind]]
        assert len(names) == len(set(names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert TEXT.match(w["why"]) and w["chips"] in (1, 4)
    for c in MAN["configs"]:
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
    for m in MAN["per_layer"]:
        assert TEXT.match(m["layer"])


def test_metrics_contract():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"], \
                (m["name"], cell)
    for cell in cells:
        reported = [m for m in MAN["end_to_end"]
                    if "workloads" not in m or cell in m["workloads"]]
        assert any(m["name"] == "setup_s" for m in reported)
        assert len(reported) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in MAN["per_layer"])
    layers = {}
    for m in MAN["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) == {"scheduler", "model step", "kernels", "device"}


def test_cells_and_chips():
    configs = {c["name"] for c in MAN["configs"]}
    used = {w["config"] for w in MAN["workloads"]}
    assert used == configs
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_every_file_found_by_name():
    man = Manifest(ROOT)
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/configs/")
        cfg = man.config(c["name"])
        assert cfg["name"] == c["name"]
        arch = man.arch(cfg["arch"])
        for fn in ("dims", "describe", "make_params", "program_config",
                   "final_hidden", "step_flops", "layer_calls"):
            assert callable(getattr(arch, fn)), (cfg["arch"], fn)
        hash(arch.dims(cfg))
        for k in c["reduced"]:
            assert k in cfg
    for w in MAN["workloads"]:
        mix = man.traffic(w["traffic"])
        assert mix["loop"] in ("open", "closed")
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert callable(man.metric_reader(m["name"]))
    for k in man.kernels():
        assert k.PHASE in ("decode", "prefill") and callable(k.cost)
        assert isinstance(k.PATH, str) and re.compile(k.EVENT)
    assert "TPU v5 lite" in json.loads(
        (ROOT / "bench" / "peaks.json").read_text())["devices"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        Manifest(ROOT).peaks("TPU v9000")


def test_configs_keep_published_widths():
    # widths of the program's own published configs, unchanged
    from repro import configs
    for name in ("starcoder2-7b", "qwen3-8b"):
        cfg = Manifest(ROOT).config(name)
        pub = configs.get_config(name)
        assert cfg["hidden_size"] == pub.d_model
        assert cfg["intermediate_size"] == pub.d_ff
        assert cfg["num_attention_heads"] == pub.n_heads
        assert cfg["num_key_value_heads"] == pub.n_kv_heads
        assert cfg["head_dim"] == pub.head_dim
        assert cfg["vocab_size"] == pub.vocab_size
        assert cfg["published"]["num_hidden_layers"] == pub.n_layers
        assert cfg["num_hidden_layers"] * 2 == pub.n_layers
