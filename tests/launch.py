"""The one launcher of the tests' multi-process jobs: ``tpurun(n, args)`` runs
``python -m ompi_tpu.tools.tpurun -n <n> ...`` from the checkout, pinned to
the CPU and with no rank identity inherited, in a session of its own, and
``run(cmd)`` any other command the same way.  A job that outlasts its timeout
is killed as a process group, the launcher with the ranks under it (``tpurun``
starts them in its own group), and reaped before ``TimeoutExpired`` is
raised again: a timed-out case leaves nothing spinning beside the tests that
follow it.
"""
import os
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: what a rank reads its identity from: a job never inherits the caller's
_IDENTITY = ("OTPU_RANK", "OTPU_NPROCS", "OTPU_COORD")


def run(cmd, timeout, env=None):
    """``subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)`` in
    a new session; on a timeout the whole session's process group is killed
    and the launcher reaped before ``TimeoutExpired`` is raised again."""
    with subprocess.Popen(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True,
                          start_new_session=True) as proc:
        try:
            out, err = proc.communicate(timeout=timeout)
        except BaseException:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.kill()
            # the pipes close when the last rank that held them is gone
            proc.communicate()
            raise
    return subprocess.CompletedProcess(cmd, proc.returncode, out, err)


def job_env(env=None):
    """The tests' environment with the CPU pinned, no rank identity, and
    ``env``'s entries over it (``None`` takes a variable away)."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return {k: v for k, v in full.items()
            if v is not None and (k not in _IDENTITY or k in (env or {}))}


def tpurun(n, args, timeout=120, extra=(), env=None):
    """One ``tpurun`` job of ``n`` ranks (``None``: ``extra`` says how
    many): ``args`` is a script's path, run by this interpreter, or the
    ranks' whole argument list; ``extra`` are ``tpurun``'s own options and
    ``env`` entries added to the job's environment."""
    if isinstance(args, (str, os.PathLike)):
        args = [sys.executable, str(args)]
    count = [] if n is None else ["-n", str(n)]
    return run([sys.executable, "-m", "ompi_tpu.tools.tpurun", *count,
                *extra, *args], timeout, job_env(env))
