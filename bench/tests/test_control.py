"""The control comes out as not correct.  Each cell's configuration at its
published widths and depth (a chain's input cut to 32 pixels), at batch 2
on the CPU: the program's reading is within the cell's limit, and the
control's — the reference computed at ``high`` (bf16_3x) in the program's
place — is above it.  On the chip, at the cells' own sizes,
``bench/calibrate.py`` takes the readings the limits were set from."""
import pytest

import tiny
from bench import lib

# cell -> (tiny cell whose loop and traffic it runs, configuration)
CELLS = {"vgg16.b32": ("chain.closed", "vgg16"),
         "table1_ar.b128": ("parallel.closed", "table1_ar")}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_control_fails_the_limit_the_program_meets(cell, seed):
    limits = lib.load_json("cells", cell + ".json")
    tiny_cell, config = CELLS[cell]
    cfg = tiny.real_config(config)
    r, loop = tiny.make_run(tiny_cell, seed, cfg)
    assert loop.control(r) > limits["limits"]["max_rel_err"]
    out = tiny.run(tiny_cell, seed=seed, cfg=cfg, limits=limits)
    assert out["correct"], out["check"]
