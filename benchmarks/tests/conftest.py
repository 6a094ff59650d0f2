"""``pytest benchmarks/tests`` runs on the CPU at tiny sizes (outside the
repository's tier-1 suite)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from perceiver_io_tpu.utils.platform import ensure_cpu_only  # noqa: E402

ensure_cpu_only(device_count=1)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
