"""The control of ``rank1-partitioned``'s comparison, kept at a size a
test run holds: the reference computed in bfloat16 and put in the
program's place must come out as not correct, on every seed, while the
program differs nowhere (``tools/control.py``; the chip's readings at
the cell's own size are in PERF.md section 2)."""
import numpy as np

from harness import data
from harness import protocol as pt
from tools import control


def test_the_control_fails_where_the_program_passes(tiny_root):
    table = control.read("rank1-partitioned", [7, 2147483999, 3200000123],
                         platform="cpu", root=tiny_root)
    assert len(table) == 4
    for name, row in table.items():
        assert row["program"] == [0, 0, 0], name
        # nearly every multiple of 2**-12 loses bits in bfloat16
        assert min(row["control"]) > 0.9 * row["compared"], name


def test_fine16_values_need_sixteen_bits_and_sum_exactly():
    import jax
    import jax.numpy as jnp

    x = np.asarray(data.values(jax.random.PRNGKey(5), (4, 4096), "float32",
                               "SUM", data.FINE16))
    assert x.dtype == np.float32 and -8 <= x.min() and x.max() < 8
    units = x * 4096
    assert np.array_equal(units, np.round(units))       # multiples of 2**-12
    rounded = x.astype(jnp.bfloat16).astype(np.float32)
    assert np.count_nonzero(rounded != x) > 0.9 * x.size
    # any order of summation over the ranks gives the same bits
    assert np.array_equal(x.sum(axis=0, dtype=np.float32),
                          x[::-1].sum(axis=0, dtype=np.float32))
    assert np.array_equal(x.sum(axis=0, dtype=np.float32),
                          x.astype(np.float64).sum(axis=0).astype(np.float32))
    # whole numbers from -8 to 8, every other point's values, pass
    # through bfloat16 unchanged: they could not tell the control apart
    whole = np.asarray(data.values(jax.random.PRNGKey(5), (4, 64),
                                   "float32", "SUM"))
    assert np.array_equal(whole.astype(jnp.bfloat16).astype(np.float32),
                          whole)
    import pytest
    with pytest.raises(ValueError, match="fine16"):
        data.values(jax.random.PRNGKey(5), (4,), "int32", "BAND", "fine16")
    assert pt.SAMPLE == 1 << 20
