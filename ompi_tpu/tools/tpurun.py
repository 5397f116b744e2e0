"""tpurun — the mpirun-equivalent launcher.

The reference's ``mpirun`` is a symlink to PRRTE's ``prte``
(``ompi/tools/mpirun/Makefile.am:3-7``): it launches processes and gives
them a PMIx server.  tpurun does the same: starts the coordination
service (``ompi_tpu.rte.coord.CoordServer``), spawns N ranks with
identity in the environment, streams their output with rank prefixes,
and tears the job down on first failure (mpirun's kill-job-on-abort
behavior).

Multi-host launch (``--hostfile``) composes this the way mpirun's
ssh/rsh plm does (``prte`` launching remote daemons): the head parses
the hostfile, assigns ranks to hosts byslot, binds the coord service on
a routable interface, and drives one *child launcher* per remote host
through the launch agent (``ssh`` by default) —
``tpurun --child-of HEAD:PORT --ranks 4,5,…`` — which spawns its local
ranks with ``OTPU_COORD`` pointing back at the head.  Rank output flows
back through the agent's stdout.  ``--launch-agent local`` runs the
child launchers as plain subprocesses, exercising the identical
head/child protocol without sshd (CI; emulated multi-node).
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys
import threading
import time


def _parse_hostfile(path: str) -> list:
    """mpirun hostfile lines: ``host [slots=N]``; # comments."""
    hosts = []
    with open(path) as fh:
        for raw in fh:
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            slots = 1
            for tok in parts[1:]:
                if tok.startswith("slots="):
                    slots = int(tok.split("=", 1)[1])
            hosts.append((parts[0], slots))
    if not hosts:
        raise SystemExit(f"tpurun: hostfile {path!r} lists no hosts")
    return hosts


def _assign_ranks(hosts: list, nprocs: int, oversubscribe: bool) -> list:
    """Byslot assignment (mpirun's default RMAPS policy): fill each
    host's slots in hostfile order; ``--oversubscribe`` wraps around."""
    total = sum(s for _, s in hosts)
    if total == 0:
        raise SystemExit("tpurun: hostfile has zero total slots")
    if nprocs > total and not oversubscribe:
        raise SystemExit(
            f"tpurun: {nprocs} ranks exceed {total} hostfile slots "
            "(use --oversubscribe, like mpirun)")
    out = [[] for _ in hosts]
    r = 0
    while r < nprocs:
        for i, (_, slots) in enumerate(hosts):
            take = min(slots, nprocs - r)
            out[i].extend(range(r, r + take))
            r += take
            if r >= nprocs:
                break
    return out


_LOCAL_NAMES = ("localhost", "127.0.0.1", "::1")


def _is_local_host(host: str) -> bool:
    return (host in _LOCAL_NAMES or host == socket.gethostname()
            or host == socket.getfqdn())


def _parse_pset(spec: str, nprocs: int) -> tuple:
    """``--pset NAME:RANKS`` → (name, [ranks]); RANKS is a comma list
    with ranges, e.g. ``workers:0,2-3``."""
    name, sep, ranks_s = spec.partition(":")
    if not sep or not name or not ranks_s:
        raise SystemExit(f"tpurun: bad --pset {spec!r} "
                         "(expected NAME:RANKS, e.g. workers:0,2-3)")
    ranks: list = []
    for tok in ranks_s.split(","):
        a, dash, b = tok.partition("-")
        try:
            lo = int(a)
            hi = int(b) if dash else lo
        except ValueError:
            raise SystemExit(f"tpurun: bad rank token {tok!r} in "
                             f"--pset {spec!r}")
        if hi < lo:
            raise SystemExit(f"tpurun: reversed range {tok!r} in "
                             f"--pset {spec!r}")
        ranks.extend(range(lo, hi + 1))
    bad = [r for r in ranks if not 0 <= r < nprocs]
    if bad or len(set(ranks)) != len(ranks):
        raise SystemExit(f"tpurun: --pset {spec!r} ranks invalid for a "
                         f"{nprocs}-rank job")
    return name, ranks


def _free_port(host: str = "127.0.0.1") -> int:
    """A currently-free TCP port for the jax.distributed coordinator
    (bind-and-release; the window until rank 0 binds it is tiny and a
    collision fails loudly at initialize).  When the coordinator will
    live on a REMOTE host (rank 0 not local) the probe can only sample
    the head's port space — best effort, same as mpirun's static port
    ranges."""
    s = socket.socket()
    try:
        try:
            s.bind((host if host != "0.0.0.0" else "", 0))
        except OSError:
            s.bind(("", 0))    # remote rank-0 host: probe locally
        return s.getsockname()[1]
    finally:
        s.close()


def _monitor(procs_list, rank_of, *, enable_recovery: bool, label: str,
             on_fail=None, abort_check=None) -> int:
    """ONE monitor loop for head and child launchers (they must never
    diverge on failure policy): poll children; without recovery the
    first nonzero exit ends the job with that code; with recovery each
    death is reported once via ``on_fail(rank, rc)`` and the group
    keeps running (job fails only if nothing succeeded).
    ``abort_check()`` may return an exit code for out-of-band aborts
    (the head's coord-service MPI_Abort path)."""
    exit_code = 0
    reported: set = set()
    try:
        while True:
            snapshot = list(procs_list)
            alive = [p for p in snapshot if p.poll() is None]
            failed = [p for p in snapshot
                      if p.poll() is not None and p.returncode != 0]
            if abort_check is not None:
                code = abort_check()
                if code is not None:
                    exit_code = code
                    break
            if failed:
                if enable_recovery:
                    for p in failed:
                        rank = rank_of(p)
                        if rank not in reported:
                            reported.add(rank)
                            print(f"{label}: rank {rank} failed (exit "
                                  f"{p.returncode}); continuing "
                                  "(recovery)", file=sys.stderr)
                            if on_fail is not None:
                                on_fail(rank, p.returncode)
                else:
                    exit_code = failed[0].returncode
                    break
            if not alive:
                if enable_recovery and snapshot and not any(
                        p.returncode == 0 for p in snapshot):
                    # recovery mode, but nothing survived to completion
                    exit_code = next(p.returncode for p in snapshot
                                     if p.returncode != 0)
                break
            time.sleep(0.05)
    except KeyboardInterrupt:
        exit_code = 130
    return exit_code


def _merge_traces(server) -> None:
    """otpu-trace gather: ranks publish their Chrome trace payloads into
    the CoordServer KV space at finalize; the head aligns their clocks
    (each payload carries the rank's measured offset to the coord clock,
    the mpisync min-RTT estimate) and writes one merged timeline plus a
    text skew report next to the per-rank files."""
    import json

    from ompi_tpu.runtime import trace

    raw = server.collect(trace._KV_KEY)
    if not raw:
        return

    payloads = []
    for rank in sorted(raw):
        try:
            payloads.append(json.loads(raw[rank]))
        except (TypeError, ValueError):
            print(f"tpurun: rank {rank} published an unreadable trace",
                  file=sys.stderr)
    if not payloads:
        return
    tdir = payloads[0].get("metadata", {}).get("trace_dir", "otpu-trace")
    try:
        os.makedirs(tdir, exist_ok=True)
        merged_path = os.path.join(tdir, "trace_merged.json")
        # carry each rank's ring-wrap counter into the merged file's
        # metadata: otpu_analyze leads its report with it (a silently
        # truncated timeline makes critical paths lie)
        overwritten = {
            str(p["metadata"]["rank"]):
                int(p["metadata"].get("events_overwritten", 0) or 0)
            for p in payloads if p.get("metadata", {}).get("rank")
            is not None}
        with open(merged_path, "w") as f:
            json.dump({"traceEvents": trace.merge_timelines(payloads),
                       "metadata": {"ranks": sorted(raw),
                                    "clock": "coord-server",
                                    "events_overwritten": {
                                        r: n for r, n in
                                        overwritten.items() if n}}}, f)
        report_path = os.path.join(tdir, "trace_skew.txt")
        report = trace.skew_report(payloads)
        with open(report_path, "w") as f:
            f.write(report)
    except OSError as exc:
        print(f"tpurun: cannot write merged trace: {exc}", file=sys.stderr)
        return
    print(f"tpurun: merged timeline of {len(payloads)} ranks -> "
          f"{merged_path}; skew report -> {report_path}", file=sys.stderr)


def _merge_monitoring(server) -> None:
    """Job-wide communication matrix: ranks publish their monitoring
    matrices into the coord KV at finalize; the head sums them and
    prints ONE table (superseding the per-rank atexit dumps)."""
    import json

    from ompi_tpu.runtime import monitoring

    raw = server.collect(monitoring._KV_KEY)
    if not raw:
        return

    payloads = []
    for rank in sorted(raw):
        try:
            payloads.append(json.loads(raw[rank]))
        except (TypeError, ValueError):
            pass
    if payloads:
        print("tpurun: " + monitoring.merged_summary(
            payloads, server.nprocs), file=sys.stderr)


def _gather_flight(server) -> None:
    """Flight-recorder gather: crashing/surviving ranks publish their
    post-mortem dumps into the coord KV; the head merges them with the
    coord service's own timestamped event view into one clock-aligned
    bundle (victim's last trace events ordered against the survivors'
    recovery spans on the coord clock)."""
    import json

    from ompi_tpu.runtime import flight as flight_mod

    raw = server.collect(flight_mod._KV_KEY)
    if not raw:
        return
    dumps = {}
    for rank in sorted(raw):
        try:
            dumps[rank] = json.loads(raw[rank])
        except (TypeError, ValueError):
            print(f"tpurun: rank {rank} published an unreadable flight "
                  "dump", file=sys.stderr)
    if not dumps:
        return
    # clock-aligned merged event tail: each dump's trace tail wrapped
    # as a per-rank payload and run through THE timeline merger (one
    # alignment implementation, shared with _merge_traces)
    from ompi_tpu.runtime import trace

    merged = trace.merge_timelines([
        {"traceEvents": d.get("trace_tail", []),
         "metadata": {"rank": rank,
                      "clock_offset_us": d.get("clock_offset_us", 0.0)}}
        for rank, d in dumps.items()])
    bundle = {
        "dumps": {str(r): d for r, d in dumps.items()},
        "coord": server.flight_view(),
        "merged_tail": merged,
        "clock": "coord-server",
    }
    fdir = next(iter(dumps.values())).get("flight_dir", "otpu-crash")
    try:
        os.makedirs(fdir, exist_ok=True)
        path = os.path.join(fdir, "bundle.json")
        with open(path, "w") as f:
            json.dump(bundle, f)
    except OSError as exc:
        print(f"tpurun: cannot write flight bundle: {exc}",
              file=sys.stderr)
        return
    reasons = ", ".join(f"rank {r}: {d.get('reason')}"
                        for r, d in sorted(dumps.items()))
    print(f"tpurun: flight-recorder bundle of {len(dumps)} dump(s) "
          f"({reasons}) -> {path}", file=sys.stderr)


def _teardown(procs_list, pumps, exit_code: int) -> None:
    """Shared job teardown: kill survivors on failure (mpirun's
    kill-job-on-abort), drain cleanly on success, join the pumps."""
    for p in procs_list:
        if p.poll() is None:
            if exit_code:
                p.kill()
            else:
                p.wait()
    for p in procs_list:
        p.wait()
    for t in pumps:
        t.join(timeout=2)


def _child_main(args, cmd) -> int:
    """Child-launcher mode (``--child-of``): the per-host daemon of the
    multi-host launch — spawn this host's rank subset with OTPU_COORD
    pointing at the head's coord service, stream rank-prefixed output
    (the head passes it through verbatim), and mirror the head's
    failure policy: first failure tears the local group down (the head
    then sees our nonzero exit), or with --enable-recovery each death
    is published as a proc_failed event and the group keeps running."""
    ranks = [int(r) for r in args.ranks.split(",") if r != ""]
    env_base = dict(os.environ)
    import ompi_tpu as _pkg
    pkg_root = os.path.dirname(
        os.path.dirname(os.path.abspath(_pkg.__file__)))
    env_base["PYTHONPATH"] = (
        env_base["PYTHONPATH"] + os.pathsep + pkg_root
        if env_base.get("PYTHONPATH") else pkg_root)
    env_base["OTPU_NPROCS"] = str(args.nprocs)
    env_base["OTPU_COORD"] = args.child_of
    if args.node_id:
        env_base["OTPU_NODE_ID"] = args.node_id
    if not args.with_tpu:
        env_base["JAX_PLATFORMS"] = "cpu"
    if args.device_world:
        # flags, not env, carry this over a launch agent (ssh forwards
        # no environment); the coordinator address rides the coord KV
        env_base["OTPU_DEVICE_WORLD"] = "1"
        if args.local_devices > 0:
            env_base["XLA_FLAGS"] = (
                env_base.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{args.local_devices}").strip()
    for name, value in args.mca:
        env_base["OTPU_MCA_" + name.removeprefix("otpu_")] = value

    procs: dict[subprocess.Popen, int] = {}
    pumps = []

    def _pump(rank: int, stream) -> None:
        for line in iter(stream.readline, b""):
            sys.stdout.write(f"[{rank}] {line.decode(errors='replace')}")
            sys.stdout.flush()

    for rank in ranks:
        env = dict(env_base)
        env["OTPU_RANK"] = str(rank)
        if args.bind_to != "none":
            env["OTPU_BIND_POLICY"] = args.bind_to
            env["OTPU_LOCAL_NRANKS"] = str(len(ranks))
        try:
            p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT)
        except OSError as exc:
            print(f"tpurun[child]: cannot launch {cmd[0]!r}: {exc}",
                  file=sys.stderr)
            for q in procs:
                q.kill()
            return 127
        procs[p] = rank
        t = threading.Thread(target=_pump, args=(rank, p.stdout),
                             daemon=True)
        t.start()
        pumps.append(t)

    def publish_failed(rank: int, rc: int) -> None:
        try:
            from ompi_tpu.rte.coord import CoordClient

            # args.child_of is the head's address: OTPU_COORD lives
            # only in the ranks' env, not this launcher's os.environ
            h, _, prt = args.child_of.rpartition(":")
            c = CoordClient(addr=(h, int(prt)))
            c.event_publish("proc_failed",
                            {"rank": rank, "origin": "launcher"})
            c.close()
        except Exception as exc:
            print(f"tpurun[child]: failure publish failed: {exc}",
                  file=sys.stderr)

    exit_code = _monitor(
        procs, procs.__getitem__,
        enable_recovery=args.enable_recovery,
        label="tpurun[child]", on_fail=publish_failed)
    _teardown(list(procs), pumps, exit_code)
    return exit_code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpurun", description="Launch an ompi_tpu multi-process job")
    ap.add_argument("-n", "-np", type=int, default=1, dest="nprocs")
    ap.add_argument("--hostfile", default=None,
                    help="Multi-host launch: 'host [slots=N]' per line "
                         "(mpirun hostfile format); remote hosts get a "
                         "child launcher via --launch-agent")
    ap.add_argument("--launch-agent", default="ssh -o BatchMode=yes",
                    dest="launch_agent",
                    help="Command that runs the child launcher on a "
                         "remote host ('<agent> <host> <command>'); the "
                         "special value 'local' runs child launchers as "
                         "plain subprocesses (emulated multi-node / CI)")
    ap.add_argument("--coord-host", default=None,
                    help="Address remote ranks use to reach the coord "
                         "service (default: this host's primary address "
                         "when a hostfile names remote hosts)")
    ap.add_argument("--remote-python", default=None,
                    help="Python interpreter for child launchers "
                         "(default: this interpreter for 'local' agent, "
                         "python3 over ssh)")
    ap.add_argument("--wdir", default=None,
                    help="Working directory child launchers cd into "
                         "(default over ssh: current directory)")
    ap.add_argument("--oversubscribe", action="store_true",
                    help="Allow more ranks than hostfile slots")
    # internal: child-launcher mode (one per remote host)
    ap.add_argument("--child-of", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--ranks", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--node-id", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--mca", action="append", nargs=2, default=[],
                    metavar=("NAME", "VALUE"),
                    help="Set an MCA variable for all ranks")
    ap.add_argument("--tag-output", action="store_true", default=True)
    ap.add_argument("--coord-port", type=int, default=0)
    ap.add_argument("--fake-nodes", type=int, default=0, metavar="K",
                    help="Partition ranks into K emulated nodes (sets "
                         "OTPU_NODE_ID=rank*K//nprocs per rank) so the "
                         "hierarchical coll/han path can be exercised on "
                         "one host, like mpirun --oversubscribe for han")
    ap.add_argument("--bind-to", choices=("none", "core"), default="none",
                    help="CPU binding policy: 'core' gives each rank a "
                         "contiguous block of allowed cores via the hwloc "
                         "analog (ompi_tpu.base.hwloc); 'none' (default) "
                         "leaves ranks unbound, like --oversubscribe")
    ap.add_argument("--enable-recovery", action="store_true",
                    help="ULFM mode: a dying rank is reported as a "
                         "proc_failed event instead of tearing down the job "
                         "(mpirun --enable-recovery)")
    ap.add_argument("--pset", action="append", default=[],
                    metavar="NAME:RANKS",
                    help="Publish a user process set (MPI-4 pset) under "
                         "NAME with the given ranks (comma list with "
                         "ranges: 'workers:0,2-3'); sessions resolve it "
                         "via Session.group_from_pset")
    ap.add_argument("--router-ranks", default=None, metavar="RANKS",
                    dest="router_ranks",
                    help="Serving role flag: publish the given ranks "
                         "(comma list with ranges) as the "
                         "'mpi://serving/router' pset — "
                         "ompi_tpu.serving.roles() resolves placement "
                         "from it")
    ap.add_argument("--worker-ranks", default=None, metavar="RANKS",
                    dest="worker_ranks",
                    help="Serving role flag: publish the given ranks as "
                         "the 'mpi://serving/workers' pset (the serving "
                         "router's model-shard worker table)")
    ap.add_argument("--pool", action="append", default=[],
                    metavar="MODEL:RANKS", dest="pool",
                    help="Fleet pool flag (repeatable): publish the "
                         "given ranks as the "
                         "'mpi://serving/pool/<MODEL>' pset — one "
                         "per-model worker pool of the serving fleet "
                         "(ompi_tpu.serving.fleet resolves pool "
                         "placement from these, the way roles() "
                         "resolves the router).  Same RANKS syntax as "
                         "--pset: comma list with ranges, "
                         "'llama:1,3-4'")
    ap.add_argument("--device-world", action="store_true",
                    dest="device_world",
                    help="Boot a multi-process device world: every rank "
                         "initializes jax.distributed (coordinator "
                         "address published through the coord service, "
                         "process_id from the rank map) so the global "
                         "device mesh — and coll/xla collectives — span "
                         "processes")
    ap.add_argument("--local-devices", type=int, default=0,
                    dest="local_devices", metavar="K",
                    help="With --device-world on the CPU backend: give "
                         "each rank K virtual devices "
                         "(xla_force_host_platform_device_count)")
    ap.add_argument("--with-tpu", action="store_true",
                    help="Let the rank see the host's accelerators "
                         "instead of being pinned to the CPU backend.  "
                         "One rank per host only: a chip belongs to one "
                         "process, and every rank would open every chip")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]

    if args.child_of:
        return _child_main(args, cmd)

    from ompi_tpu.rte.coord import CoordServer

    hosts = rank_groups = None
    if args.hostfile:
        hosts = _parse_hostfile(args.hostfile)
        rank_groups = _assign_ranks(hosts, args.nprocs,
                                    args.oversubscribe)
    per_host = ([len(rr) for rr in rank_groups] if rank_groups is not None
                else [args.nprocs])
    if args.with_tpu and max(per_host) > 1:
        # refuse at launch what would otherwise hang: N ranks on one
        # host would each open every chip of it
        ap.error("--with-tpu supports one rank per host: a chip belongs "
                 "to one process.  The supported chip path is the "
                 "single-process device world (run the program without "
                 "tpurun: ompi_tpu.init() then drives every local chip "
                 "as one rank each); per-chip process placement is not "
                 "implemented")
        any_remote = (args.launch_agent != "local"
                      and any(not _is_local_host(h) for h, _ in hosts))
        # remote ranks must reach the coord service: bind every
        # interface and advertise a routable address instead of loopback
        bind = "0.0.0.0" if any_remote else "127.0.0.1"
        server = CoordServer(args.nprocs, host=bind,
                             port=args.coord_port)
        port = server.addr[1]
        host = args.coord_host or (
            socket.gethostbyname(socket.gethostname()) if any_remote
            else "127.0.0.1")
    else:
        server = CoordServer(args.nprocs, port=args.coord_port)
        host, port = server.addr

    # process-set registry (MPI-4 psets, served to sessions by the coord
    # service): the builtin world set, one set per node the rank map
    # names, and any user sets.  mpi://SELF stays client-resolved (its
    # membership is per-process).
    server.publish_pset("mpi://WORLD", range(args.nprocs),
                        source="builtin")
    node_ranks: dict = {}
    for rank in range(args.nprocs):
        if rank_groups is not None:
            node = next(h for (h, _), rr in zip(hosts, rank_groups)
                        if rank in rr)
        elif args.fake_nodes > 0:
            node = f"node{rank * args.fake_nodes // args.nprocs}"
        else:
            node = socket.gethostname()
        node_ranks.setdefault(node, []).append(rank)
    for node, ranks_on in node_ranks.items():
        server.publish_pset(f"mpi://host/{node}", ranks_on, source="host")
    for spec_s in args.pset:
        pname, pranks = _parse_pset(spec_s, args.nprocs)
        server.publish_pset(pname, pranks, source="user")
    # serving role psets (ompi_tpu.serving.roles) — same RANKS syntax
    for flag, pset_name in ((args.router_ranks, "mpi://serving/router"),
                            (args.worker_ranks, "mpi://serving/workers")):
        if flag:
            _, pranks = _parse_pset(f"serving:{flag}", args.nprocs)
            server.publish_pset(pset_name, pranks, source="user")
    # fleet pool psets (ompi_tpu.serving.fleet.pool_specs_from_psets):
    # one mpi://serving/pool/<model> set per --pool flag
    for spec_s in args.pool:
        model, pranks = _parse_pset(spec_s, args.nprocs)
        server.publish_pset(f"mpi://serving/pool/{model}", pranks,
                            source="user")

    if args.device_world:
        # jax.distributed coordinator lives INSIDE rank 0's process;
        # advertise the address where rank 0 will actually run.  When
        # rank 0 is on the head but OTHER hosts are remote, loopback
        # would be unreachable for them — reuse the coord service's
        # already-routable advertised host in that case.
        jax_host = host
        if rank_groups is not None and args.launch_agent != "local":
            r0_host = next(h for (h, _), rr in zip(hosts, rank_groups)
                           if 0 in rr)
            if not _is_local_host(r0_host):
                jax_host = r0_host
        server.kv_put(-1, "__jax_coord__",
                      f"{jax_host}:{_free_port(jax_host)}")

    env_base = dict(os.environ)
    # Ranks must be able to import ompi_tpu no matter how tpurun itself was
    # found (installed, -m from the repo, …).  Appended, not prepended: the
    # user's own PYTHONPATH entries keep shadowing rights.
    import ompi_tpu as _pkg
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(_pkg.__file__)))
    env_base["PYTHONPATH"] = (
        env_base["PYTHONPATH"] + os.pathsep + pkg_root
        if env_base.get("PYTHONPATH") else pkg_root)
    env_base["OTPU_NPROCS"] = str(args.nprocs)
    env_base["OTPU_COORD"] = f"{host}:{port}"
    if not args.with_tpu:
        env_base["JAX_PLATFORMS"] = "cpu"
    if args.device_world:
        env_base["OTPU_DEVICE_WORLD"] = "1"
        if args.local_devices > 0:
            env_base["XLA_FLAGS"] = (
                env_base.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{args.local_devices}").strip()
    for name, value in args.mca:
        env_base["OTPU_MCA_" + name.removeprefix("otpu_")] = value

    procs: list[subprocess.Popen] = []
    proc_rank: dict = {}            # Popen -> global rank | node label
    pumps: list[threading.Thread] = []

    def _pump(rank, stream) -> None:
        # child launchers (rank None) already prefix their ranks: raw
        prefix = "" if rank is None else f"[{rank}] "
        for line in iter(stream.readline, b""):
            sys.stdout.write(prefix + line.decode(errors="replace"))
            sys.stdout.flush()

    def _launch(rank, env: dict, argv=None) -> subprocess.Popen:
        p = subprocess.Popen(argv or cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        proc_rank[p] = rank       # before append: the monitor loop reads
        procs.append(p)           # proc_rank for any proc it can see
        t = threading.Thread(
            target=_pump,
            args=(rank if isinstance(rank, int) else None, p.stdout),
            daemon=True)
        t.start()
        pumps.append(t)
        return p

    def _spawn_handler(spawn_cmd, ranks, job, extra_env) -> None:
        """MPI_Comm_spawn execution: launch new global ranks as their own
        job (their own COMM_WORLD), wired to the same coord server.

        ``spawn_cmd`` is one argv (every rank runs it) or a per-rank list
        of argvs (MPI_Comm_spawn_multiple: one child world, several
        executables)."""
        per_rank = (list(spawn_cmd)
                    if spawn_cmd and isinstance(spawn_cmd[0], (list, tuple))
                    else [list(spawn_cmd)] * len(ranks))
        if len(per_rank) != len(ranks):
            raise ValueError(
                f"spawn got {len(per_rank)} argvs for {len(ranks)} ranks")
        for i, rank in enumerate(ranks):
            env = dict(env_base)
            env.update({k: str(v) for k, v in extra_env.items()})
            env["OTPU_RANK"] = str(rank)
            env["OTPU_JOB"] = job
            env["OTPU_JOB_RANKS"] = ",".join(str(r) for r in ranks)
            env["OTPU_NPROCS"] = str(len(ranks))
            if args.fake_nodes > 0:
                env["OTPU_NODE_ID"] = f"node{rank % args.fake_nodes}"
            _launch(rank, env, argv=list(per_rank[i]))

    server.set_spawn_handler(_spawn_handler)

    def _abort_launch(what: str, exc) -> int:
        print(f"tpurun: cannot launch {what!r}: {exc}", file=sys.stderr)
        for q in procs:
            q.kill()
        server.close()
        return 127

    if args.hostfile:
        # one child launcher per hostfile entry (the ssh plm's remote
        # daemon); each spawns its rank subset against our coord addr
        for (host_name, _), ranks in zip(hosts, rank_groups):
            if not ranks:
                continue
            run_local = (args.launch_agent == "local"
                         or _is_local_host(host_name))
            # locally-executed children keep THIS interpreter (venv);
            # only a genuinely remote host falls back to PATH's python3
            rpy = args.remote_python or (
                sys.executable if run_local else "python3")
            child = [rpy, "-m", "ompi_tpu.tools.tpurun",
                     "--child-of", f"{host}:{port}",
                     "--ranks", ",".join(str(r) for r in ranks),
                     "-n", str(args.nprocs), "--node-id", host_name]
            if args.enable_recovery:
                child.append("--enable-recovery")
            if args.with_tpu:
                child.append("--with-tpu")
            if args.device_world:
                child.append("--device-world")
                if args.local_devices > 0:
                    child += ["--local-devices", str(args.local_devices)]
            if args.bind_to != "none":
                child += ["--bind-to", args.bind_to]
            for name, value in args.mca:
                child += ["--mca", name, value]
            child += ["--"] + cmd
            if run_local:
                argv_full = child
            else:
                wdir = args.wdir or os.getcwd()
                argv_full = args.launch_agent.split() + [
                    host_name,
                    f"cd {shlex.quote(wdir)} && {shlex.join(child)}"]
            try:
                _launch(f"node:{host_name}", env_base, argv=argv_full)
            except OSError as exc:
                return _abort_launch(argv_full[0], exc)
    else:
        for rank in range(args.nprocs):
            env = dict(env_base)
            env["OTPU_RANK"] = str(rank)
            if args.bind_to != "none":
                env["OTPU_BIND_POLICY"] = args.bind_to
                env["OTPU_LOCAL_NRANKS"] = str(args.nprocs)
            if args.fake_nodes > 0:
                env["OTPU_NODE_ID"] = \
                    f"node{rank * args.fake_nodes // args.nprocs}"
            try:
                _launch(rank, env)
            except OSError as exc:
                return _abort_launch(cmd[0], exc)

    def publish_failed(rank, rc) -> None:
        # ULFM: report the death, keep the job running — the
        # PRRTE-daemon-detects-child-death path of the reference.
        # Child launchers publish their OWN ranks' failures; a dead
        # child launcher (non-int label) is only reported.
        if isinstance(rank, int):
            server.publish("proc_failed",
                           {"rank": rank, "origin": "launcher"})

    exit_code = _monitor(
        procs, proc_rank.__getitem__,
        enable_recovery=args.enable_recovery, label="tpurun",
        on_fail=publish_failed,
        abort_check=lambda: server.aborted)
    _teardown(procs, pumps, exit_code)
    _merge_traces(server)
    _merge_monitoring(server)
    _gather_flight(server)
    server.close()
    if exit_code:
        print(f"tpurun: job terminated with exit code {exit_code}",
              file=sys.stderr)
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
