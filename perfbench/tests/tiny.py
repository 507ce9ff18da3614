"""Tiny cells for the CPU tests: the cell files' traffic with a small
configuration of the same family, on CPU devices."""
from harness import cell as cell_lib

TINY = {
    "grid": [16, 8, 8, 8], "modes": [4, 2, 2, 3], "width": 8,
    "decoder_dim": 16, "x_stats": {"mean": [-0.84, 0.004], "std": [1.56, 0.06]},
}


def cell(workload: str, **overrides) -> cell_lib.Cell:
    c = cell_lib.load(workload)
    c.config = dict(c.config, **TINY, **overrides)
    c.traffic = dict(c.traffic, pool=600)
    return c
