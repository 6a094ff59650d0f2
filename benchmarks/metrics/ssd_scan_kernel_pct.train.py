"""Whether the stack's Mamba-2 mixers run their scan through the Pallas kernel pair (``ops/pallas_ssd.py``: a chunk's decay matrix, ``C B^T`` and state in VMEM, forward and backward) or through the XLA einsums: the program's gauge, set where the model is built from the mixer's own resolver (``ops/mamba2.scan_impl``), a constant of the traced program; 100 the kernels, 0 the einsums, nothing where the stack has no such mixer or the program publishes no such gauge."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("ssd_scan_kernel_pct")
