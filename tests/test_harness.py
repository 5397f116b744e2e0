"""The tests' own harness: a launch that outlasts its timeout leaves no rank
behind (``tests/launch.py``), a worker lets its compiled programs go before
their memory mappings reach the kernel's limit (``tests/conftest.py``), what
``tests/built.py`` hands out a donating step cannot spoil and what it makes
once a session is made once, a kept step or program is traced once and does
not see a later patch where a fresh one does, and a module's users of one
module-scoped fixture are collected side by side."""
import json
import os
import subprocess
import sys
import textwrap
import time
import types

import jax
import numpy as np
import pytest

import built
import conftest
import launch
from test_olmoe_train import F32 as OLMOE

from ompi_tpu.parallel import train


def alive_in_group(pgid: int) -> list:
    """The processes of a process group that are not zombies, from /proc."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, _, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except OSError:
            continue                    # gone between the listing and the read
        if int(pgrp) == pgid and state != "Z":
            found.append(int(pid))
    return found


def test_a_timed_out_launch_leaves_no_rank_behind(tmp_path):
    script = tmp_path / "sleeper.py"
    script.write_text(textwrap.dedent(f"""
        import os, time
        with open(r"{tmp_path}/" + os.environ["OTPU_RANK"], "w") as f:
            f.write(f"{{os.getpid()}} {{os.getpgid(0)}}")
        time.sleep(120)
    """))
    with pytest.raises(subprocess.TimeoutExpired):
        launch.tpurun(2, script, timeout=3)
    ranks = [(tmp_path / str(r)).read_text().split() for r in range(2)]
    (pgid,) = {int(group) for _, group in ranks}
    # a session of its own: killing the group cannot reach the tests
    assert pgid != os.getpgid(0)
    # SIGKILL has been sent to each; a rank is gone a moment after
    deadline = time.monotonic() + 5
    while alive_in_group(pgid) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert alive_in_group(pgid) == []


def test_a_launch_that_ends_in_time_is_subprocess_runs(tmp_path):
    script = tmp_path / "hello.py"
    script.write_text("import os, sys\n"
                      "print('rank', os.environ['OTPU_RANK'], sys.argv[1:],"
                      " os.environ.get('HELLO'), os.environ.get('OTPU_COORD')"
                      " is not None)\nsys.exit(int(os.environ['OTPU_RANK']))")
    r = launch.tpurun(2, [sys.executable, str(script), "a"], timeout=60,
                      extra=("--mca", "otpu_trace_enable", "0"),
                      env={"HELLO": "there", "OTPU_RANK": "7"})
    assert r.returncode == 1 and "terminated with exit code 1" in r.stderr
    assert "rank 0 ['a'] there True" in r.stdout
    assert launch.job_env({"XLA_FLAGS": None}).get("XLA_FLAGS") is None
    assert "OTPU_RANK" not in launch.job_env()


def test_built_params_survive_a_donating_step():
    want = jax.device_get(train.init_model_params(OLMOE, 5))
    first = built.params(OLMOE, 5)
    moved = jax.jit(lambda tree: jax.tree.map(lambda a: a * 0 - 1, tree),
                    donate_argnums=0)(first)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(first))
    assert all((np.asarray(leaf) == -1).all()
               for leaf in jax.tree.leaves(moved))
    second = built.params(OLMOE, 5)
    for got, kept in zip(jax.tree.leaves(second), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(got), kept)
    assert built.step(OLMOE) is built.step(OLMOE)
    assert built.program(train.adamw) is built.program(train.adamw)


FACTOR = 2


def scaled(x):
    return x * FACTOR


def test_what_is_kept_is_traced_once_and_what_is_fresh_now(monkeypatch):
    """A kept program holds what its function read when a shape was first
    traced; a test that patches what a trace reads calls the function
    itself, or builds a step of its own."""
    me = sys.modules[__name__]
    ref, x = built.programs(me), jax.numpy.ones((4,))
    assert float(ref.scaled(x)[0]) == 2
    monkeypatch.setattr(me, "FACTOR", 3)
    assert float(ref.scaled(x)[0]) == 2         # the patch is not seen
    assert float(ref.plain.scaled(x)[0]) == 3
    assert float(ref.scaled(jax.numpy.ones((5,)))[0]) == 3   # a new shape
    # one kept step a (configuration, devices), however it is asked for
    assert built.step(OLMOE) is built.step(OLMOE, 1) \
        is built.step(OLMOE, devices=1)
    mine, other = built.fresh_step(OLMOE), built.fresh_step(OLMOE)
    assert mine[0] is not other[0] and mine[0] is not built.step(OLMOE)[0]


def test_a_worker_lets_its_programs_go_before_the_kernels_limit():
    """Each compiled program holds mappings, and ``release_programs`` gives
    them back: the fixture that calls it when a module ends keeps a worker
    under ``vm.max_map_count``, at which XLA's next compile dies."""
    with open("/proc/sys/vm/max_map_count") as f:
        assert conftest.MAPPINGS < int(f.read()) // 2
    x = jax.numpy.ones((8,))
    held = [jax.jit(lambda x, n=n: x * n + 1) for n in range(40)]
    for program in held:
        program(x)
    before, same = conftest.release_programs(above=10 ** 9)
    assert before == same
    before, after = conftest.release_programs(above=0)
    assert after <= before - 40
    # what was compiled is compiled again when asked for
    assert float(jax.jit(lambda x: x.sum())(x)) == 8


def test_what_is_made_once_a_session_is_read_by_the_next_worker(tmp_path):
    made, name = [], f"harness-{os.getpid()}"

    def make():
        made.append(1)
        return {"rows": [1, 2], "path": str(tmp_path)}

    assert built.shared(name, make) == built.shared(name, make) \
        == {"rows": [1, 2], "path": str(tmp_path)}
    assert made == [1]
    # another process of the session reads it, and makes nothing
    code = ("import built, json; built.SESSION_DIR = %r; "
            "print(json.dumps(built.shared(%r, lambda: 1 / 0)))"
            % (built.SESSION_DIR, name))
    done = subprocess.run([sys.executable, "-c", code], text=True,
                          capture_output=True, cwd=os.path.dirname(__file__),
                          env=dict(os.environ, PYTHONPATH=launch.REPO))
    assert json.loads(done.stdout) == make(), done.stderr
    # a maker that raises leaves nothing behind for the next caller
    with pytest.raises(ZeroDivisionError):
        built.shared(name + "-broken", lambda: 1 / 0)
    assert built.shared(name + "-broken", lambda: 3) == 3


def stub_items(spec):
    """Items as the collection hook reads them: ``spec`` is (module, name,
    fixtures taken as arguments, {parameter: value}) a row; a fixture whose
    name ends in ``_m`` is module-scoped."""
    scope = lambda name, item: (
        [types.SimpleNamespace(scope="module", baseid=item.module)]
        if name.endswith("_m")
        else [types.SimpleNamespace(scope="module", baseid="tests")]
        if name == "fx_everyones"
        else [types.SimpleNamespace(scope="function", baseid=item.module)]
        if name.startswith("fx") else None)
    session = types.SimpleNamespace(_fixturemanager=types.SimpleNamespace(
        getfixturedefs=scope))
    return [types.SimpleNamespace(
        module=module, name=name, nodeid=f"{module}::{name}",
        session=session, fixturenames=["fx_everyones", *takes],
        callspec=types.SimpleNamespace(params=params))
        for module, name, takes, params in spec]


def test_a_modules_users_of_a_module_fixture_are_collected_together():
    items = stub_items([
        ("a", "a0", (), {}),
        ("a", "a1", ("rows_m",), {}),
        ("a", "a2", ("fx_plain",), {"n": 3}),
        ("a", "a3", ("other_m",), {}),
        ("b", "b0", ("rows_m",), {}),
        ("a", "a4", ("request",), {"rows": "rows_m", "case": "other_m x"}),
        ("a", "a5", ("other_m", "rows_m"), {}),
        ("a", "a6", ("request",), {"which": "other_m"}),
        ("b", "b1", (), {}),
        ("b", "b2", ("rows_m",), {}),
    ])
    items.append(stub_items([("c", "c0", ("fx_plain",), {})])[0])
    items.insert(0, stub_items([("d", "d0", (), {"n": 1})])[0])
    conftest.pytest_collection_modifyitems(items)
    # a module keeps its places; in them, the users of no module fixture
    # of the file's own, then each fixture's in the order the module first
    # takes them, a user of two after the first's; the order within a group
    # stands
    assert [i.name for i in items] == [
        "d0", "a0", "a2", "a1", "a4", "b1", "a5", "a3", "a6", "b0", "b2",
        "c0"]
    assert conftest._module_fixtures(items[4]) == ["rows_m"]


def test_the_offline_compiles_files_are_dealt_first():
    items = stub_items([
        ("tests/test_osc.py", "o0", (), {}),
        ("tests/test_pallas_aot.py", "p0", ("rows_m",), {}),
        ("tests/test_pallas_aot.py", "p1", (), {}),
        ("tests/test_pallas_aot_cells.py", "c0", (), {}),
        ("tests/test_part.py", "q0", (), {}),
    ])
    conftest.pytest_collection_modifyitems(items)
    assert [i.name for i in items] == ["p1", "p0", "c0", "o0", "q0"]
