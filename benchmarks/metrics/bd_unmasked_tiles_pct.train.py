"""Share of the tiles the block-diffusion attention kernels visit that run with no mask at all, every pair of the tile visible (the program's gauge ``bd_unmasked_tiles_pct``, set where the model is built from the tile classes the kernels branch on, ``ops/pallas_attention._bd_tile_class``, over the grid they build): a constant of the traced program; 70.0 at 16,384 positions in query tiles of 512 and key tiles of 1,024; 0 on the blocked XLA path, which runs no kernel; nothing where the program trains no block-diffusion objective."""

from benchmarks import components_decoder_lm


def read(ctx):
    return components_decoder_lm.gauge("bd_unmasked_tiles_pct")
