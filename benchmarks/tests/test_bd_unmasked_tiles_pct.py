"""The reader of ``bd_unmasked_tiles_pct.train``: the program's gauge, and
None where the program publishes none (another family's step, or a program
from before the kernels branched on a tile's class)."""

import importlib.util
import json
import os

from perceiver_io_tpu import obs

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "bd_unmasked_tiles_pct"


def _reader():
    path = os.path.join(HERE, "..", "metrics", f"{NAME}.train.py")
    spec = importlib.util.spec_from_file_location(f"{NAME}_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_reader_returns_the_gauge_or_nothing():
    read = _reader()
    obs.get_registry().remove(NAME)
    assert read({}) is None
    gauge = obs.get_registry().gauge(NAME)
    for share in (70.0, 0.0):
        gauge.set(share)
        assert read({}) == share
    obs.get_registry().remove(NAME)


def test_a_process_that_built_no_block_diffusion_model_reads_nothing():
    from benchmarks.configs import nemotron_h
    from benchmarks.run import load_config

    obs.get_registry().remove(NAME)
    nemotron_h.param_shapes(load_config("nemotron_twotower_30b_a3b_ar_ep16"))
    assert _reader()({}) is None


def test_declared_for_the_sdar_cell_alone():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = declared[f"{NAME}.train"]
    assert entry["source"] == "program_counter" and entry["moves"] == "train_samples_per_s"
    assert (entry["layer"], entry["unit"], entry["better"]) == ("kernels", "%", "higher")
    assert entry["workloads"] == ["sdar_30b_a3b_chat_bd4_ep8_train"]
