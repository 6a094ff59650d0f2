"""The reader of ``ssd_scan_kernel_pct.train``: the program's gauge, and None
where the program publishes none (another family's step, or a program from
before the scan had kernels)."""

import importlib.util
import json
import os

from perceiver_io_tpu import obs

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "ssd_scan_kernel_pct"


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
    for share in (100.0, 0.0):
        gauge.set(share)
        assert read({}) == share
    obs.get_registry().remove(NAME)


def test_declared_for_the_nemotron_cell_alone():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    entry = declared[f"{NAME}.train"]
    assert entry["source"] == "program_counter" and entry["moves"] == "train_samples_per_s"
    assert entry["layer"] == "model step"
    assert entry["workloads"] == ["nemotron_twotower_30b_a3b_ar_ep16_train"]
