"""The state-space scan's share of its RECURRENCE roofline (``components_nemotron_h.ssd_scan_roofline_pct``): the work counted as the recurrence needs it (``flops_nemotron_h.ssd_scan``), not as the chunked algorithm does it, over ``ssd_scan_device_ms.train``."""

from benchmarks import components_nemotron_h


def read(ctx):
    return components_nemotron_h.ssd_scan_roofline_pct(ctx)
