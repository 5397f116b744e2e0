#!/usr/bin/env python3
"""Look at the device ops of a trace before trusting what is read of them.

    python3 benchmark/tools/describe_ops.py <cell> [--events 20]
        [--map <scope_map.json>] [--pattern <regex>]

For the newest trace a ``--trace 1`` run of ``<cell>`` left under
``.bench_out/trace/<cell>/``, a device plane: the first ``XLA Ops`` events
with the stats the event itself carries (what ``jax.profiler.ProfileData``
gives) and the stats of the event's **metadata** (read from the file's
wire format: ``ProfileData`` does not give them; on a v5e the path of a
fusion's root is there, as ``tf_op``, with the HLO proto off).  Then, with
the program's scope map (``.bench_out/<cell>.step.scope_map.json``, which
a run writes where the program gives one; ``harness/scopes.py``), the
twenty largest unnamed, inherited and mixed ops and what the mixed ones
mix: what to look at when ``step.unnamed_share`` or ``step.mixed_share``
is high.  ``--pattern`` also prints the ms a step of the ops it matches
by (scope chain, pass): the cross-check of a metric that selects ops by
name (``^otpu_attn_block_backward``, ``^ragged-dot``) against the scopes
they lie under."""
import argparse
import json
import os
import re
import struct
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import scopes, tracered  # noqa: E402


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one protobuf message: an int
    for a varint or a fixed field, a memoryview for a length-delimited."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        else:
            size = 8 if wire == 1 else 4
            value, at = int.from_bytes(buf[at:at + size], "little"), at + size
        yield number, wire, value


def _map_entry(buf):
    """The value message of a ``map<int64, Message>`` entry."""
    return next(v for n, _, v in fields(buf) if n == 2)


def _stat(buf, stat_names):
    """(name, value) of one XStat (tsl/profiler/protobuf/xplane.proto)."""
    name, value = "?", None
    for n, wire, v in fields(buf):
        if n == 1:
            name = stat_names.get(v, str(v))
        elif n == 2:                     # double
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif n in (3, 4):
            value = v
        elif n == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif n == 6:
            value = f"<{len(v)} bytes>"
        elif n == 7:
            value = stat_names.get(v, v)    # a reference to a name
    return name, value


def metadata_stats(path: str) -> dict:
    """{plane: {event name: {stat: value}}} of the TPU device planes'
    event metadata.  The lines (the events) are skipped unread."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for n, _, plane in fields(space):
        if n != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pn, _, v in fields(plane):
            if pn == 2:
                name = bytes(v).decode()
            elif pn == 4:
                metas.append(_map_entry(v))
            elif pn == 5:
                meta = {fn: fv for fn, _, fv in fields(_map_entry(v))}
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not tracered.DEVICE_PLANE_RE.match(name):
            continue
        events = out.setdefault(name, {})
        for meta in metas:
            event, stats = "", {}
            for fn, _, fv in fields(meta):
                if fn == 2:
                    event = bytes(fv).decode("utf-8", "replace")
                elif fn == 5:
                    key, value = _stat(fv, stat_names)
                    stats[key] = value
            events[event] = stats
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--events", type=int, default=20)
    ap.add_argument("--map")
    ap.add_argument("--pattern")
    args = ap.parse_args()
    out_dir = os.path.join(os.path.dirname(BENCH_DIR), ".bench_out")
    log_dir = os.path.join(out_dir, "trace", args.cell)
    path = tracered.find_xplane(log_dir)
    print(path, os.path.getsize(path), "bytes")
    from jax.profiler import ProfileData

    metadata = metadata_stats(path)
    for plane in ProfileData.from_file(path).planes:
        if not tracered.DEVICE_PLANE_RE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != tracered.OPS_LINE:
                continue
            seen = set()
            for event in line.events:
                if event.name in seen:
                    continue
                seen.add(event.name)
                short = lambda v: v if not isinstance(v, str) else v[:160]
                print(json.dumps({
                    "plane": plane.name, "op": tracered.short_op(event.name),
                    "event_stats": {k: short(v) for k, v in event.stats},
                    "metadata_stats": {
                        k: short(v) for k, v in metadata.get(
                            plane.name, {}).get(event.name, {}).items()}}))
                if len(seen) >= args.events:
                    break
    map_file = args.map or os.path.join(
        out_dir, f"{args.cell}.{scopes.MAP_TABLE}.json")
    if not os.path.exists(map_file):
        print(f"no scope map at {map_file}: a run writes one where the "
              "program gives it (or pass --map)")
        return 0
    with open(map_file, encoding="utf-8") as f:
        maps = json.load(f)
    if "ops" in maps:                   # one map, as step.scopes() gives it
        maps = {maps["module"]: maps}
    events = tracered.load_xplane(path)
    with open(os.path.join(log_dir, "calls.json"), encoding="utf-8") as f:
        said = json.load(f)
    events["calls"] = said.get("calls", said)
    table = scopes.reduce_scopes(events, maps, list(events["calls"]),
                                 top_ops=1 << 30 if args.pattern else 10)
    if table is None:
        print("no traced step in the trace")
        return 0
    busy = table["busy_ms_per_step"]
    print(f"busy {busy:.3f} ms a step over {table['steps']} steps; rows "
          f"{table['rows_ms_per_step']:.3f}")
    for kind in ("unnamed", "compiler", "no_entry", "inherited", "mixed"):
        ms = table[f"{kind}_ms_per_step"]
        print(f"{kind}: {ms:.3f} ms a step, {100 * ms / busy:.2f}%")
        for name, op_ms in table[f"{kind}_top_ops"][:20]:
            print(f"    {op_ms:9.3f}  {name}")
    print("what the mixed fusions mix:")
    for mix, ms in table["mixed_by_kinds_ms_per_step"][:20]:
        print(f"    {ms:9.3f}  {mix}")
    if args.pattern:
        pattern = re.compile(args.pattern)
        total = 0.0
        print(f"ops matching {args.pattern!r}, ms a step by (chain, pass):")
        for row in table["rows"]:
            ms = sum(v for n, v in row["top_ops"] if pattern.search(n))
            if ms:
                total += ms
                print(f"    {ms:9.3f}  {row['pass']}  "
                      + "/".join(row["chain"]))
        print(f"    {total:9.3f}  in all, {100 * total / busy:.3f}% of busy")
    return 0


if __name__ == "__main__":
    sys.exit(main())
