"""treematch-style rank reordering: cart_create(reorder=True) places
row-major grid neighbors on the same node (topo/treematch's objective)."""
import sys
import textwrap
from pathlib import Path

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_cart_reorder_groups_nodes(tmp_path):
    """Ranks interleaved across two nodes (0,2 on n0; 1,3 on n1): with
    reorder=True each 2x2 cart ROW must be node-pure; without it the
    identity mapping leaves rows split across nodes."""
    script = tmp_path / "tm.py"
    script.write_text(textwrap.dedent("""
        import os
        # node interleave BEFORE the runtime reads it
        os.environ['OTPU_NODE_ID'] = f"n{int(os.environ['OTPU_RANK']) % 2}"
        import numpy as np, ompi_tpu
        w = ompi_tpu.init()
        me_node = os.environ['OTPU_NODE_ID']

        cart = w.cart_create([2, 2], reorder=True)
        i, j = cart.cart_coords()
        # all row members agree on a node; columns cross nodes
        rows = cart.allgather(np.array(
            [i, 1 if me_node == 'n1' else 0], np.int64))
        rows = np.asarray(rows).reshape(4, 2)
        for row in (0, 1):
            vals = {int(n) for r, n in rows if r == row}
            assert len(vals) == 1, (row, rows)
        # and the two rows are on DIFFERENT nodes
        n0 = {int(n) for r, n in rows if r == 0}
        n1 = {int(n) for r, n in rows if r == 1}
        assert n0 != n1, rows

        # without reorder the identity mapping splits every row
        plain = w.cart_create([2, 2], reorder=False)
        pi, pj = plain.cart_coords()
        prows = np.asarray(plain.allgather(np.array(
            [pi, 1 if me_node == 'n1' else 0], np.int64))).reshape(4, 2)
        mixed = any(len({int(n) for r, n in prows if r == row}) == 2
                    for row in (0, 1))
        assert mixed, prows
        print(f"treematch OK rank {w.rank}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("treematch OK") == 4
