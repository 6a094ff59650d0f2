"""The reader of ``moe_bounded_path_pct.train``: the program's gauge, and
None where the program publishes none (another family's step, or a program
from before the bounded buffer)."""

import importlib.util
import os

from perceiver_io_tpu import obs

HERE = os.path.dirname(os.path.abspath(__file__))


def _reader():
    path = os.path.join(HERE, "..", "metrics", "moe_bounded_path_pct.train.py")
    spec = importlib.util.spec_from_file_location("moe_bounded_path_pct_train", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_reader_returns_the_gauge_or_nothing():
    read = _reader()
    obs.get_registry().remove("moe_bounded_path_pct")
    assert read({}) is None
    gauge = obs.get_registry().gauge("moe_bounded_path_pct")
    for share in (100.0, 80.0, 0.0):
        gauge.set(share)
        assert read({}) == share
