"""Each configuration's work function, pinned to hand counts at a small
shape, so that a kernel rewrite cannot change what a roofline divides
by."""
import numpy as np

from bench.families.flash_attention.work import work as attention_work
from bench.families.stencil3d25.work import work as stencil_work


def test_stencil_counts_taps_and_compulsory_bytes():
    # r=1 on (2, 3, 4): 7 taps, a multiply and an add each, at 24 points
    need = stencil_work({"r": 1, "domain": (2, 3, 4), "dtype": "float32"})
    assert need["flops"] == 2 * 7 * 24 == 336
    # padded input (4 * 5 * 6) read once, output (2 * 3 * 4) written once
    assert need["bytes"] == 4 * (120 + 24) == 576


def test_stencil_at_the_cell_shape():
    need = stencil_work({"r": 4, "domain": (512, 512, 640),
                         "dtype": "float32"})
    assert need["bytes"] == 4 * (520 * 520 * 648 + 512 * 512 * 640)
    assert need["flops"] == 50 * 512 * 512 * 640


def test_attention_counts_the_pairs_the_causal_mask_keeps():
    s = 5
    kept = int(np.tril(np.ones((s, s))).sum())        # 15 pairs
    shape = {"batch": 2, "q_heads": 4, "kv_heads": 2, "seq": s,
             "head_dim": 8, "causal": True, "dtype": "bfloat16"}
    need = attention_work(shape)
    # q.k and p.v: 2 * head_dim operations each, per kept pair and head
    assert need["flops"] == 2 * 4 * kept * (2 * 8 + 2 * 8) == 3840
    # q and o over the query heads, k and v over the kv heads, 2 B each
    assert need["bytes"] == 2 * (2 * 2 * 4 * 5 * 8 + 2 * 2 * 2 * 5 * 8) == 1920
    assert attention_work(dict(shape, causal=False))["flops"] == \
        2 * 4 * 25 * 32


def test_attention_at_the_cell_shape():
    need = attention_work({"batch": 8, "q_heads": 32, "kv_heads": 8,
                           "seq": 4096, "head_dim": 128, "causal": True,
                           "dtype": "bfloat16"})
    assert need["flops"] == 4 * 8 * 32 * (4096 * 4097 // 2) * 128
    assert need["bytes"] == 2 * (2 * 8 * 32 * 4096 * 128
                                 + 2 * 8 * 8 * 4096 * 128)
