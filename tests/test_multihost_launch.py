"""Multi-host launch path: tpurun --hostfile drives one child launcher
per host (the ssh/rsh plm analog, ``ompi/tools/mpirun/Makefile.am:3-7``
→ prte remote daemons).  ``--launch-agent local`` runs the identical
head→child→coord protocol as plain subprocesses — real child
launchers, distinct node ids, ranks joining one world through the
head's coord service — without needing sshd in CI.
"""
import functools
import os
import subprocess
import sys
import textwrap

from launch import tpurun

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: the argument list says how many ranks, after the hostfile
_tpurun = functools.partial(tpurun, None, timeout=180)


def test_hostfile_ring_end_to_end(tmp_path):
    """The VERDICT done-criterion: tpurun --hostfile h.txt -n 8
    examples/ring.py works end-to-end."""
    hf = tmp_path / "h.txt"
    hf.write_text("nodeA slots=4\nnodeB slots=4\n")
    r = _tpurun(["--hostfile", str(hf), "--launch-agent", "local",
                 "-n", "8", sys.executable,
                 os.path.join(REPO, "examples", "ring.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "token now 0" in r.stdout
    assert r.stdout.count("exiting") == 8


def test_hostfile_node_ids_and_world(tmp_path):
    """Ranks land on their assigned hosts (byslot), see distinct node
    ids, and still form ONE world through the head's coord service."""
    hf = tmp_path / "hosts.txt"
    hf.write_text(textwrap.dedent("""\
        # two emulated nodes
        alpha slots=2
        beta  slots=2
    """))
    script = tmp_path / "whoami.py"
    script.write_text(textwrap.dedent("""
        import os
        import numpy as np
        import ompi_tpu
        w = ompi_tpu.init()
        node = os.environ.get("OTPU_NODE_ID")
        out = w.allgather(np.array([w.rank], np.int64))
        print(f"RANK {w.rank} NODE {node} SUM "
              f"{int(np.asarray(out).sum())}")
        ompi_tpu.finalize()
    """))
    r = _tpurun(["--hostfile", str(hf), "--launch-agent", "local",
                 "-n", "4", sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    lines = sorted(ln.split("] ", 1)[1] for ln in r.stdout.splitlines()
                   if "RANK" in ln)
    # byslot: ranks 0,1 -> alpha; 2,3 -> beta; allgather sum proves one
    # world across both child launchers
    assert lines == [
        "RANK 0 NODE alpha SUM 6", "RANK 1 NODE alpha SUM 6",
        "RANK 2 NODE beta SUM 6", "RANK 3 NODE beta SUM 6"], lines


def test_hostfile_slot_guard_and_oversubscribe(tmp_path):
    hf = tmp_path / "small.txt"
    hf.write_text("one slots=1\ntwo slots=1\n")
    script = tmp_path / "ok.py"
    script.write_text("import ompi_tpu; w = ompi_tpu.init(); "
                      "print('R', w.rank); ompi_tpu.finalize()")
    # 4 ranks > 2 slots: refused, like mpirun without --oversubscribe
    r = _tpurun(["--hostfile", str(hf), "--launch-agent", "local",
                 "-n", "4", sys.executable, str(script)])
    assert r.returncode != 0
    assert "oversubscribe" in (r.stdout + r.stderr)
    # with the flag the ranks wrap around the hosts
    r = _tpurun(["--hostfile", str(hf), "--launch-agent", "local",
                 "-n", "4", "--oversubscribe",
                 sys.executable, str(script)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("R ") == 4


def test_fake_ssh_agent_contract(tmp_path):
    """The DEFAULT multi-host path (``--launch-agent ssh``) exercised
    without sshd: a fake-ssh shim stands in for ssh and asserts the
    exact contract tpurun's head relies on —

    * argv shape ``<agent words…> <host> <ONE shell command>`` (exactly
      what ``ssh host "cmd"`` accepts);
    * the command cd's into the launch cwd first (ssh starts in $HOME);
    * the child launcher is fully self-described on its command line
      (``--child-of`` coord address, ``--ranks``, ``-n``, ``--node-id``)
      with NO environment marshalling — ssh forwards none, so any env
      dependence would only fail on real clusters;

    then execs the command locally through a SCRUBBED environment (PATH/
    HOME only, like a fresh login shell), proving the remote side works
    from the command line + cwd alone."""
    shim = tmp_path / "fakessh.py"
    shim.write_text(textwrap.dedent("""
        import os, subprocess, sys

        def fail(msg):
            print("FAKESSH ASSERT:", msg, file=sys.stderr, flush=True)
            sys.exit(99)

        args = sys.argv[1:]
        # tpurun split the agent string into words; ours ends with the
        # ssh-style option so the full ssh argv shape is exercised
        if args[:2] != ["-o", "BatchMode=yes"]:
            fail(f"agent words not forwarded: {args[:2]}")
        if len(args) != 4:
            fail(f"expected '<opts> <host> <command>', got {args}")
        host, command = args[2], args[3]
        if host not in ("ghostA", "ghostB"):
            fail(f"unexpected host {host}")
        wdir = os.environ["FAKESSH_WDIR"]
        if not command.startswith(f"cd {wdir} && "):
            fail(f"command must cd into the launch cwd: {command[:80]}")
        for needle in ("-m ompi_tpu.tools.tpurun", "--child-of",
                       "--ranks", "--node-id " + host):
            if needle not in command:
                fail(f"{needle!r} missing from: {command}")
        if "OTPU_" in command:
            fail("identity must ride flags, not exported env")
        with open(os.environ["FAKESSH_LOG"], "a") as log:
            print(host, file=log, flush=True)
        # exec like sshd: fresh login-ish env, nothing marshalled
        env = {k: v for k, v in os.environ.items()
               if k in ("PATH", "HOME", "LANG")}
        sys.exit(subprocess.run(["/bin/sh", "-c", command],
                                env=env).returncode)
    """))
    hf = tmp_path / "hosts.txt"
    hf.write_text("ghostA slots=2\nghostB slots=2\n")
    log = tmp_path / "shim.log"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               FAKESSH_LOG=str(log), FAKESSH_WDIR=REPO)
    r = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.tpurun",
         "--hostfile", str(hf),
         "--launch-agent",
         f"{sys.executable} {shim} -o BatchMode=yes",
         "--remote-python", sys.executable,
         "-n", "4", sys.executable,
         os.path.join(REPO, "examples", "ring.py")],
        capture_output=True, text=True, timeout=180, cwd=REPO, env=env)
    assert "FAKESSH ASSERT" not in r.stderr, r.stderr
    assert r.returncode == 0, r.stdout + r.stderr
    assert "token now 0" in r.stdout, r.stdout
    assert r.stdout.count("exiting") == 4
    # one agent invocation per remote host
    assert sorted(log.read_text().split()) == ["ghostA", "ghostB"]


def test_hostfile_child_failure_tears_down(tmp_path):
    hf = tmp_path / "hosts.txt"
    hf.write_text("n1 slots=2\nn2 slots=2\n")
    script = tmp_path / "die.py"
    script.write_text(textwrap.dedent("""
        import os, sys, time
        import ompi_tpu
        w = ompi_tpu.init()
        if w.rank == 3:
            sys.exit(7)        # a rank on the SECOND child dies
        time.sleep(30)         # others would hang forever
    """))
    r = _tpurun(["--hostfile", str(hf), "--launch-agent", "local",
                 "-n", "4", sys.executable, str(script)], timeout=120)
    # the child reports exit 7, the head tears the whole job down
    assert r.returncode != 0
    assert "terminated" in r.stderr or r.returncode == 7
