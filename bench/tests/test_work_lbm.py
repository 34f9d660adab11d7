"""The D3Q15 LBM's work function, pinned to hand counts, so that a kernel
rewrite cannot change what its roofline divides by."""
from bench.families.lbm_d3q15.work import work


def test_lbm_counts_the_update_and_compulsory_bytes():
    # (1, 2, 3): 6 updates, halo-padded (3, 4, 5) = 60 cells
    need = work({"domain": (1, 2, 3), "dtype": "float32"})
    # gradient 6, normal 7, sharpening 3; rest velocity 1 + 3, six axis
    # velocities 1 + 4 + 3 each, eight corners 3 + 4 + 3 each
    per_update = 6 + 7 + 3 + 4 + 6 * 8 + 8 * 10
    assert per_update == 148
    assert need["flops"] == 148 * 6
    # 15 padded PDFs and the padded phase read once, 15 PDFs written once
    assert need["bytes"] == 4 * (15 * 60 + 60 + 15 * 6) == 4200


def test_lbm_at_the_tiny_size():
    need = work({"domain": (8, 32, 128), "dtype": "float32"})
    assert need["flops"] == 148 * 8 * 32 * 128
    assert need["bytes"] == 4 * (16 * 10 * 34 * 130 + 15 * 8 * 32 * 128) \
        == 4794880


def test_lbm_at_the_cell_shape():
    need = work({"domain": (256, 256, 256), "dtype": "float32"})
    assert need["bytes"] == 4 * (16 * 258 ** 3 + 15 * 256 ** 3) \
        == 2_105_737_728
    assert need["flops"] == 148 * 256 ** 3
