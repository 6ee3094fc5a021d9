"""Of the (query, key) pairs in the tiles that the banded flash
kernel's grid computes, the share that the band holds: the program's
counters `attention.window.band_pairs` over `.tile_pairs`
(`layers/transformer.GatedAttention` adds both at every traced call of
the kernel under a window, static: `ops/flash_attention.window_tiling`).
What is left is masked work at the band's two edges; it falls as the
blocks shrink against the window. None where no call took the banded
kernel."""


def read(run):
  from tensor2robot_tpu import telemetry

  counts = telemetry.registry().scalars("attention.window.")
  tiles = counts.get("attention.window.tile_pairs", 0.0)
  if not tiles:
    return None
  return 100.0 * counts.get("attention.window.band_pairs", 0.0) / tiles
