"""Distance, in points, of the share of the tokens x top-k assignments computed on this chip (the program's gauge of the window's last step, mean over the expert layers) from held / published experts x 100 (``components_decoder_lm.local_assignment_gap_pct``): 0 under a router that favours no expert."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.local_assignment_gap_pct()
