"""Forward operation count of the Perona scoring pass (bench/flops.py)."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402

MODEL = {"code_dim": 32, "hidden": 64, "heads": 4, "tag_hops": 2,
         "n_types": 6, "predecessors": 3}


def test_row_count_by_hand():
    # F=94 features, A=12 edge attributes, K=32, H=64, T=6, P=3:
    # encoder 94*64 + 64*32, query 32*32, keys/values 2*3*32*32, edge
    # terms 2*3*12*32, attention 2*3*32, TAG 3*32*32 + 2*3*32, output
    # and root 2*32*32, anomaly head 32*64 + 64, probe 32*6; two
    # operations per multiply-add
    macs = (94 * 64 + 64 * 32 + 32 * 32 + 2 * 3 * 32 * 32
            + 2 * 3 * 12 * 32 + 2 * 3 * 32 + 3 * 32 * 32 + 2 * 3 * 32
            + 2 * 32 * 32 + 32 * 64 + 64 + 32 * 6)
    assert flops.perona_row_flops(94, 12, **MODEL) == 2 * macs == 50688


def test_stack_count_scales_with_real_rows_only():
    # a stack of 1,024 requests x 128 padded rows of which 102 are real
    assert flops.perona_flops(1024 * 102, 94, 12, MODEL) \
        == 1024 * 102 * 50688
