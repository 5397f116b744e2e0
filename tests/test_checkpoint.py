"""Sharded checkpoint/restore (SURVEY §5.4 — the gap the reference leaves)."""
import os
import sys
import textwrap
from pathlib import Path

import numpy as np

from launch import tpurun as _tpurun

REPO = Path(__file__).resolve().parent.parent


def test_device_world_save_load_reshard(tmp_path):
    """A pytree of sharded jax arrays round-trips and restores onto a
    DIFFERENT sharding (the elasticity property)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ompi_tpu.parallel import checkpoint as ckpt

    devs = np.array(jax.devices()[:8])
    mesh8 = Mesh(devs, ("x",))
    sh8 = NamedSharding(mesh8, P("x"))
    tree = {
        "layer0": {"w": jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8), sh8),
            "b": jax.device_put(np.ones(8, np.float32), sh8)},
        "step": np.int64(7),
    }
    d = str(tmp_path / "ck")
    ckpt.save(d, tree)
    assert os.path.exists(os.path.join(d, "manifest.json"))

    # restore as plain numpy
    back = ckpt.load(d)
    assert np.array_equal(back["layer0"]["w"],
                          np.arange(64, dtype=np.float32).reshape(8, 8))
    assert int(back["step"]) == 7

    # restore onto a 2x4 mesh with a different partitioning
    mesh24 = Mesh(devs.reshape(2, 4), ("a", "b"))
    sh24 = NamedSharding(mesh24, P("a", "b"))

    def shard_for(path):
        return sh24 if path.endswith("/w") else NamedSharding(mesh24, P())

    back2 = ckpt.load(d, sharding=shard_for)
    w2 = back2["layer0"]["w"]
    assert isinstance(w2, jax.Array) and w2.sharding == sh24
    assert np.array_equal(np.asarray(w2),
                          np.arange(64, dtype=np.float32).reshape(8, 8))


def test_multiprocess_sharded_save(tmp_path):
    """4 ranks each contribute their Shard through collective I/O; the
    dense checkpoint restores in a plain single process."""
    d = tmp_path / "mpck"
    script = tmp_path / "saver.py"
    script.write_text(textwrap.dedent(f"""
        import numpy as np, ompi_tpu
        from ompi_tpu.parallel import checkpoint as ckpt
        w = ompi_tpu.init()
        r = w.rank
        gi, gj = divmod(r, 2)
        block = np.full((3, 5), float(r), np.float64)
        tree = {{
            "w": ckpt.Shard(block, [gi * 3, gj * 5], [6, 10]),
            "lr": np.float64(0.25),     # replicated leaf
        }}
        ckpt.save({str(d)!r}, tree, comm=w)
        print(f"saved rank {{r}}")
    """))
    r = _tpurun(4, [sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("saved") == 4

    from ompi_tpu.parallel import checkpoint as ckpt

    back = ckpt.load(str(d))
    w = back["w"]
    assert w.shape == (6, 10)
    for rr in range(4):
        gi, gj = divmod(rr, 2)
        blk = w[gi * 3:(gi + 1) * 3, gj * 5:(gj + 1) * 5]
        assert np.all(blk == float(rr)), (rr, blk)
    assert float(back["lr"]) == 0.25
