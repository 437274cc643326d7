"""The benchmark's copy of the pool is the program's pool, byte for byte."""

import numpy as np

from bench.pool import POOLING, make_pool


def test_copied_pool_equals_make_dlrm_pool_byte_for_byte():
    from repro.data.synthetic import make_dlrm_pool
    raw, zipf = make_pool(856, 0)
    want = make_dlrm_pool(0)
    assert raw.dtype == want.dtype and raw.shape == want.shape
    assert raw.tobytes() == want.tobytes()
    assert zipf.shape == (856,)
    assert np.all((zipf >= 0.35) & (zipf <= 1.7))
    assert raw[:, POOLING].max() <= 200
