import numpy as np

import datagen
from incubator_predictionio_tpu.ops.rowblocks import plan_layout

CFG = dict(n_users=2000, n_items=300, n_ratings=20000, shape_seed=9,
           user_degree_sigma=1.3, item_degree_sigma=1.8)


def test_same_degrees_and_plan_for_two_seeds_other_triples():
    a = datagen.ratings(CFG, 1)
    b = datagen.ratings(CFG, 2**31 + 12345)
    assert len(a[0]) == len(b[0]) == CFG["n_ratings"]
    for side, n in ((0, CFG["n_users"]), (1, CFG["n_items"])):
        ca = np.bincount(a[side], minlength=n)
        cb = np.bincount(b[side], minlength=n)
        assert (ca == cb).all() and ca.min() >= 1
        pa, pb = plan_layout(ca, 1), plan_layout(cb, 1)
        assert (pa.lengths == pb.lengths).all()
        assert (pa.bucket_rows == pb.bucket_rows).all()
        assert (pa.slot_of_row == pb.slot_of_row).all()
    assert not (a[1] == b[1]).all() and not (a[2] == b[2]).all()
    assert set(np.unique(a[2])) <= {0.5 * k for k in range(1, 11)}


def test_same_seed_same_triple():
    a, b = datagen.ratings(CFG, 77), datagen.ratings(CFG, 77)
    assert all((x == y).all() for x, y in zip(a, b))


def test_factors_from_a_large_seed_repeat():
    a = datagen.factors(64, 8, 2**31 + 5, 1, block_rows=24)
    b = datagen.factors(64, 8, 2**31 + 5, 1, block_rows=24)
    c = datagen.factors(64, 8, 5, 1, block_rows=24)
    assert (a == b).all() and not (a == c).all()
    assert a.shape == (64, 8) and not (a[:24] == a[24:48]).all()
