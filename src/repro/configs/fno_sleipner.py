"""The paper's Sleipner CO2-flow FNO (§V-B, CCS benchmark).

Paper grid 262x118x64 x 86 time steps padded to 256x128x64x88 (the original
2.1M-cell simulation grid, mesh-divisible). Inputs: binary injection-well
map (repeated along t); outputs: CO2 saturation history.
"""
from repro.core.fno import FNOConfig

CONFIG = FNOConfig(
    grid=(256, 128, 64, 88),
    modes=(24, 16, 8, 10),
    width=40,
    in_channels=1,
    out_channels=1,
    n_blocks=4,
    decoder_dim=128,
)

SHAPES = (
    ("train_b32", 32, "train"),
    ("infer_b32", 32, "infer"),
)

# One chip's share of the 8x4 pencil deployment of this config
# (fno_sleipner_2d.PENCIL_SHAPE: k_y split over 8 x-shards, k_z over 4
# y-shards), as chip_smoke.py trains and serves it on one TPU v5e chip:
#   * modes (24,2,2,10): 2*m_y = 32/8 = 4 and 2*m_z = 16/4 = 4 kept modes
#     per chip; m_x = 24 and m_t = 10 as published;
#   * grid (64,16,24,88), cut from (256,128,64,88) by memory: the largest
#     grid whose train step (batch 1, Adam state) and 2-slot serving
#     bucket each compile with >3 GB of the chip's 16 GB left beside them
#     (tests/test_tpu_compile.py); nx stays >= 2*m_x;
#   * in_channels 2: the static geomodel channel plus the well map, as a
#     ``datagen --geomodel`` store lays them out, so serving runs the deep
#     geomodel cache.
ONE_CHIP_OVERRIDES = {
    "grid": (64, 16, 24, 88), "modes": (24, 2, 2, 10), "in_channels": 2,
}
# Four chips hold a 2x2 block of the same pencil (a 2x2 --model-shards
# mesh): m_y = m_z = 4 split 2x2 keeps the same 4x4 (k_y, k_z) modes per
# chip. The grid stays (64,16,24,88) because the serial float32 reference
# runs the whole model on one chip, beside that chip's share of the
# sharded runner.
FOUR_CHIP_OVERRIDES = {
    "grid": (64, 16, 24, 88), "modes": (24, 4, 4, 10), "in_channels": 2,
}
