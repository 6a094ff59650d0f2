"""Test configuration: force CPU with 8 virtual XLA devices.

Set before jax initializes any backend so SPMD/mesh tests can exercise an
8-device mesh without TPU hardware (the JAX-native way to test sharding,
SURVEY.md §4). The chip is reached only through ``chip_smoke.py`` /
``bench.py`` on a machine that has one.

jax's persistent compilation cache is OFF for the test process AND the
processes it spawns (the environment variable is inherited): entry points
still place it (``aot.configure_compile_cache`` — where it would live is
tested), but nothing is read or written, so the AOT executable-cache tests
keep storing (stores are refused while the persistent cache is active) and
no test leaves compiles under the checkout.
"""

import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from perceiver_io_tpu.utils.platform import ensure_cpu_only

ensure_cpu_only(device_count=8)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def remat_policy_events(tmp_path):
    """A reader of the ``remat.policy`` records written since the test
    began (``models.perceiver.remat_keeps`` emits one a trace)."""
    import json

    from perceiver_io_tpu import obs

    path = tmp_path / "events.jsonl"
    obs.configure_event_log(str(path))

    def read():
        obs.configure_event_log(None)  # drains, then closes
        with open(path) as f:
            records = [json.loads(line) for line in f]
        return [r for r in records if r.get("event") == "remat.policy"]

    yield read
    obs.configure_event_log(None)
