"""Device milliseconds a step spends under ``mamba2/ssd_scan``: the state-space scan alone (from the split of ``xBC`` and ``Delta`` to ``y`` before the gate), every Mamba-2 layer's forward, recomputations and backward; a part of ``mamba2_device_ms.train``."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.ssd_scan_ms(ctx.get("summary"))
