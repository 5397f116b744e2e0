"""BENCHMARK.json and the data files it names: loading, resolving a name
to its file, and every rule of the manifest that code can check.

``run.py`` resolves a cell through this module and ``check_manifest.py``
runs :func:`validate`.  The rules are the builder's contract as far as a
program can test them, plus the harness's own (every name resolves to a
file).  A rule that fails is one line of text; an empty list passes.
"""
from __future__ import annotations

import ast
import json
import os
import re

from harness import readerkit

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
LAYER_SOURCES = E2E_SOURCES | {"program_span", "program_counter"}
CELL_KEYS = {"pool_bytes_per_point", "pool_max", "trace_rounds"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_BOUND = 0.1
MIN_BOUND = 0.01
MAX_FILE_BYTES = 64 << 10


def one_line(text, limit: int = 200) -> bool:
    """1 to ``limit`` printable ASCII characters: so one line, no tab,
    no long dash, no curly quote.  The contract asks for one line with
    no tab; ASCII is this repository's own, stricter rule (the lost PR
    22 taught that the check's idea of 'printable' is not ours)."""
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and text.isascii() and text.isprintable())


def load(root: str = REPO_ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e.get("name") == name:
            return e
    raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                   f"(has: {', '.join(e.get('name', '?') for e in entries)})")


def data_file(kind_dir: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    """``<bench_dir>/<kind_dir>/<name>.json``: how the harness finds the
    file of a traffic mix, a cell or a metric from its name alone."""
    return os.path.join(bench_dir, kind_dir, name + ".json")


def code_file(kind_dir: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, kind_dir, name + ".py")


RAW_FIELDS = ("fw_over_raw", "raw_per_call_us")   # columns of a raw twin


def metric_spec(name: str, bench_dir: str = BENCH_DIR) -> dict:
    """``metrics/<name>.json``: a ``reader`` and its ``params``."""
    return load_json(data_file("metrics", name, bench_dir))


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` that ``cell``
    reports: those with no ``workloads`` key and those that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def traffic_points(name: str, bench_dir: str = BENCH_DIR,
                   _seen: tuple = ()) -> list:
    """The points of a traffic mix, each with the ``set`` (file) it was
    written in.  A mix may ``include`` other mixes (the four-chip cell
    interleaves the small and the large set).  A point's own ``e2e``
    field, if it has one, names the end-to-end metric it counts under
    (``_check_tags``); ``set`` is the harness's, and no end-to-end
    metric may select on it.  ``inner`` is the kind that a wrapping
    ``kind`` wraps (``protocol.load_kind``)."""
    if name in _seen:
        raise ValueError(f"traffic {name!r} includes itself")
    spec = load_json(data_file("traffic", name, bench_dir))
    points = []
    for inc in spec.get("include", []):
        points += traffic_points(inc, bench_dir, _seen + (name,))
    for p in spec.get("points", []):
        points.append({**p, "set": name})
    names = [p["name"] for p in points]
    if len(set(names)) != len(names):
        raise ValueError(f"traffic {name!r}: a point name appears twice")
    return points


# -- the rules -------------------------------------------------------------
def _check_names(errors: list, what: str, entries: list) -> None:
    seen = set()
    for e in entries:
        n = e.get("name")
        if not (isinstance(n, str) and NAME_RE.match(n)):
            errors.append(f"{what} name {n!r}: 1 to 64 of letters, digits, "
                          "'_', '.', '-', not starting with '.' or '-'")
        if n in seen:
            errors.append(f"{what} name {n!r} appears twice")
        seen.add(n)


def _under_paths(rel: str, paths: list) -> bool:
    rel = os.path.normpath(rel)
    return any(rel == os.path.normpath(p)
               or rel.startswith(os.path.normpath(p) + os.sep)
               for p in paths)


def _check_metric(errors, m, keys, sources, cells) -> None:
    name = m.get("name")
    extra = set(m) - keys - {"workloads"}
    missing = keys - set(m)
    if extra or missing:
        errors.append(f"metric {name!r}: keys must be {sorted(keys)} "
                      f"(+ workloads); extra {sorted(extra)}, "
                      f"missing {sorted(missing)}")
    if not (isinstance(m.get("unit"), str) and UNIT_RE.match(m["unit"])):
        errors.append(f"metric {name!r}: unit {m.get('unit')!r} must be 1 "
                      "to 16 of letters, digits, '_', '/', '%', '.', '-'")
    if m.get("better") not in ("lower", "higher"):
        errors.append(f"metric {name!r}: better must be lower or higher")
    if m.get("source") not in sources:
        errors.append(f"metric {name!r}: source {m.get('source')!r} not in "
                      f"{sorted(sources)}")
    if "workloads" in m:
        w = m["workloads"]
        if not (isinstance(w, list) and w and all(c in cells for c in w)):
            errors.append(f"metric {name!r}: workloads {w!r} must be a "
                          "non-empty list of cell names")


def validate(manifest: dict, root: str = REPO_ROOT,
             raw_bytes: int | None = None) -> list:
    """Every rule that code can check; returns the failures as text."""
    errors: list = []
    if raw_bytes is not None and raw_bytes > MAX_FILE_BYTES:
        errors.append(f"BENCHMARK.json is {raw_bytes} bytes, over 64 KiB")
    if set(manifest) != TOP_KEYS:
        errors.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}, "
                      f"got {sorted(manifest)}")
        return errors

    # paths and command
    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errors.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if not (isinstance(p, str) and PATH_RE.match(p)) \
                or p.startswith("/") or ".." in p.split("/"):
            errors.append(f"path {p!r}: relative, at most 200 of letters, "
                          "digits, '_', '.', '-', '/', no '..'")
        elif not os.path.isdir(os.path.join(root, p)):
            errors.append(f"path {p!r} is not a directory of the repo")
    cmd = manifest["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(one_line(w) for w in cmd)):
        errors.append("command: a list of 1 to 32 one-line strings")
        cmd = []
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/"):
            errors.append(f"command word {w!r}: no absolute path, no '..'")
        elif os.path.exists(os.path.join(root, w)) \
                and not _under_paths(w, paths):
            errors.append(f"command word {w!r} names a file of the repo "
                          "outside paths")
    for p in paths:
        for dirpath, dirnames, files in os.walk(os.path.join(root, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in files:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not PATH_RE.match(rel):
                    errors.append(f"file {rel!r}: name outside letters, "
                                  "digits, '_', '.', '-', '/'")

    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 10 <= rs <= 51):
        errors.append(f"run_seconds {rs!r}: one whole number, 10 to 51")

    configs, cells = manifest["configs"], manifest["workloads"]
    e2e, layer = manifest["end_to_end"], manifest["per_layer"]
    for what, entries, lo, hi in (("configs", configs, 1, 24),
                                  ("workloads", cells, 2, 24),
                                  ("end_to_end", e2e, 1, 16),
                                  ("per_layer", layer, 1, 128)):
        if not (isinstance(entries, list) and lo <= len(entries) <= hi
                and all(isinstance(e, dict) for e in entries)):
            errors.append(f"{what}: {lo} to {hi} objects")
            return errors
    _check_names(errors, "config", configs)
    _check_names(errors, "cell", cells)
    _check_names(errors, "metric", e2e + layer)
    cell_names = {c["name"] for c in cells}
    config_names = {c["name"] for c in configs}

    # configurations
    files = set()
    for c in configs:
        name = c.get("name")
        if set(c) != CONFIG_KEYS:
            errors.append(f"config {name!r}: keys must be exactly "
                          f"{sorted(CONFIG_KEYS)}")
            continue
        if not one_line(c["source"]):
            errors.append(f"config {name!r}: source must be ONE string of 1 "
                          "to 200 printable ASCII characters, not "
                          f"{c['source']!r:.80}")
        if not one_line(c["why"]):
            errors.append(f"config {name!r}: why must be one line of 1 to "
                          "200 printable ASCII characters")
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME_RE.match(k)
                        for k in red)):
            errors.append(f"config {name!r}: reduced is at most 16 names")
            red = []
        f = c["file"]
        if not (isinstance(f, str) and PATH_RE.match(f)
                and _under_paths(f, paths)):
            errors.append(f"config {name!r}: file {f!r} must lie under "
                          "paths")
        elif f in files:
            errors.append(f"config {name!r}: file {f!r} is another "
                          "configuration's file too")
        elif not os.path.isfile(os.path.join(root, f)):
            errors.append(f"config {name!r}: file {f!r} does not exist")
        else:
            body = load_json(os.path.join(root, f))
            if not isinstance(body, dict):
                errors.append(f"config {name!r}: {f} is not a JSON object")
            else:
                for k in red:
                    if k not in body.get("reduced_from", {}):
                        errors.append(
                            f"config {name!r}: reduced key {k!r} has no "
                            f"entry under reduced_from in {f}")
        files.add(f)
        if not any(w.get("config") == name for w in cells):
            errors.append(f"config {name!r} is used by no cell")

    # cells
    pairs = set()
    for w in cells:
        name = w.get("name")
        if set(w) != WORKLOAD_KEYS:
            errors.append(f"cell {name!r}: keys must be exactly "
                          f"{sorted(WORKLOAD_KEYS)}")
            continue
        if w["config"] not in config_names:
            errors.append(f"cell {name!r}: no configuration "
                          f"{w['config']!r}")
        if not (isinstance(w["traffic"], str)
                and NAME_RE.match(w["traffic"])):
            errors.append(f"cell {name!r}: traffic {w['traffic']!r} is "
                          "not a name")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            errors.append(f"cell {name!r}: chips must be 1 or 4")
        if not one_line(w["why"]):
            errors.append(f"cell {name!r}: why must be one line of 1 to "
                          "200 printable ASCII characters")
        pair = (w["config"], w["traffic"])
        if pair in pairs:
            errors.append(f"cell {name!r}: the pair {pair} appears twice")
        pairs.add(pair)
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errors.append(f"{four} cells ask for 4 chips; of {len(cells)} "
                      f"cells at most {max(1, len(cells) // 4)} may")

    # metrics
    for m in e2e:
        _check_metric(errors, m, E2E_KEYS, E2E_SOURCES, cell_names)
        b = m.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and MIN_BOUND <= b <= MAX_BOUND):
            errors.append(f"metric {m.get('name')!r}: bound {b!r} must be "
                          f"a share from {MIN_BOUND} to {MAX_BOUND}")
    setup = [m for m in e2e if m.get("name") == "setup_s"]
    if not setup or "workloads" in setup[0] or setup[0].get("unit") != "s" \
            or setup[0].get("better") != "lower":
        errors.append("end_to_end needs setup_s (unit s, better lower) in "
                      "every cell")
    e2e_by_name = {m.get("name"): m for m in e2e}
    for m in layer:
        _check_metric(errors, m, LAYER_KEYS, LAYER_SOURCES, cell_names)
        if not one_line(m.get("layer")):
            errors.append(f"metric {m.get('name')!r}: layer must be one "
                          "line of 1 to 200 printable ASCII characters")
        moved = e2e_by_name.get(m.get("moves"))
        if moved is None:
            errors.append(f"metric {m.get('name')!r}: moves "
                          f"{m.get('moves')!r} is no end-to-end metric")
            continue
        mine = set(m.get("workloads", cell_names))
        theirs = set(moved.get("workloads", cell_names))
        if not mine <= theirs:
            errors.append(
                f"metric {m['name']!r} is reported in "
                f"{sorted(mine - theirs)} where {moved['name']!r}, the "
                "metric it moves, is not")
    for w in cells:
        name = w.get("name")
        mine = [m.get("name") for m in metrics_of(manifest, "end_to_end",
                                                  name)]
        if "setup_s" not in mine or len(mine) < 2:
            errors.append(f"cell {name!r} needs setup_s and at least one "
                          f"other end-to-end metric, has {mine}")
        if not metrics_of(manifest, "per_layer", name):
            errors.append(f"cell {name!r} reports no per-layer metric")
    return errors


def _selections(manifest: dict, cell: str, points: list, bench_dir: str):
    """(metric, its file's ``params``, the cell's points it selects) for
    every metric the cell reports whose file selects points, by the
    columns a traffic file gives (``readerkit.point_row``)."""
    rows = [readerkit.point_row(p) for p in points]
    for m in (metrics_of(manifest, "end_to_end", cell)
              + metrics_of(manifest, "per_layer", cell)):
        try:
            params = metric_spec(m["name"], bench_dir).get("params", {})
        except OSError:                 # a missing file is its own error
            continue
        if "select" in params:
            static = {"select": {k: v for k, v in params["select"].items()
                                 if k in readerkit.POINT_COLUMNS},
                      "exclude": params.get("exclude")}
            yield m["name"], params, [
                r["name"] for r in readerkit.select(rows, static)]


def points_by_metric(manifest: dict, cell: str, points: list,
                      bench_dir: str = BENCH_DIR) -> dict:
    """{metric: the names of the cell's points its file selects}: what
    a run will read, known before it runs."""
    return {name: chosen for name, _, chosen
            in _selections(manifest, cell, points, bench_dir)}


def raw_points(manifest: dict, cell: str, points: list,
               bench_dir: str = BENCH_DIR) -> set:
    """The names of the cell's points whose raw twin some per-layer
    metric of the cell reads (a ``field`` of ``RAW_FIELDS``): a traced
    run builds and times a twin for these and no others."""
    return {name for _, params, chosen
            in _selections(manifest, cell, points, bench_dir)
            if params.get("field") in RAW_FIELDS for name in chosen}


def _check_tags(errors: list, manifest: dict, cell: str, points: list,
                bench_dir: str) -> None:
    """Membership by tag.  A point's ``e2e`` names an end-to-end metric
    the cell reports; a point without one is per-layer only.  Every
    metric the cell reports whose file selects points selects at least
    one of the cell's: a metric that would silently read nothing fails
    here, not on the chip.  No end-to-end metric selects on ``set``:
    that is the name of a file, which a later PR's points cannot
    carry."""
    e2e = {m["name"] for m in metrics_of(manifest, "end_to_end", cell)}
    for p in points:
        if p.get("e2e") is not None and p["e2e"] not in e2e:
            errors.append(
                f"cell {cell!r}: point {p['name']!r} is tagged "
                f"{p['e2e']!r}, no end-to-end metric that lists the cell "
                f"(has: {', '.join(sorted(e2e))})")
    for name, params, chosen in _selections(manifest, cell, points,
                                            bench_dir):
        select = params["select"]
        if name in e2e and "set" in select:
            errors.append(f"metric {name!r}: an end-to-end metric selects "
                          "by the points' e2e tag, not by set")
        if not chosen:
            errors.append(f"metric {name!r} lists cell {cell!r} and "
                          f"selects none of its points ({select})")


def validate_harness(manifest: dict, root: str = REPO_ROOT) -> list:
    """This harness's own rules: every name in the manifest resolves to
    the file the harness will look for, every point's tag and every
    metric's selection meet in the cell (``_check_tags``), and every
    free text in those files is printable ASCII."""
    errors: list = []
    bench_dir = os.path.join(root, manifest["paths"][0])
    kinds = set()
    for w in manifest["workloads"]:
        cell = data_file("cells", w["name"], bench_dir)
        if not os.path.isfile(cell):
            errors.append(f"cell {w['name']!r}: no {cell}")
            continue
        missing = CELL_KEYS - set(load_json(cell))
        if missing:
            errors.append(f"cell {w['name']!r}: {cell} lacks "
                          f"{sorted(missing)}")
        try:
            points = traffic_points(w["traffic"], bench_dir)
            for p in points:
                kinds.update({p["kind"], p.get("inner", p["kind"])})
                if not NAME_RE.match(p["name"]):
                    errors.append(f"traffic {w['traffic']!r}: point name "
                                  f"{p['name']!r} is not a name")
            _check_tags(errors, manifest, w["name"], points, bench_dir)
        except (OSError, ValueError, KeyError) as e:
            errors.append(f"cell {w['name']!r}: traffic does not resolve: "
                          f"{e}")
    for k in sorted(kinds):
        if not os.path.isfile(code_file("kinds", k, bench_dir)):
            errors.append(f"call kind {k!r}: no kinds/{k}.py")
        else:
            errors += _check_tolerance(k, code_file("kinds", k, bench_dir))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        try:
            reader = metric_spec(m["name"], bench_dir).get("reader")
        except OSError as e:
            errors.append(f"metric {m['name']!r}: no {e.filename}")
            continue
        if not (isinstance(reader, str)
                and os.path.isfile(code_file("readers", reader, bench_dir))):
            errors.append(f"metric {m['name']!r}: reader {reader!r} has no "
                          "readers/<reader>.py")
    for sub in ("configs", "traffic", "cells", "metrics"):
        d = os.path.join(bench_dir, sub)
        for fn in sorted(os.listdir(d)) if os.path.isdir(d) else []:
            if not fn.endswith(DATA_SUFFIXES):
                errors.append(f"{sub}/{fn}: a data file ends in one of "
                              f"{DATA_SUFFIXES}")
                continue
            for text in _strings(load_json(os.path.join(d, fn))):
                if not (text.isascii() and text.isprintable()):
                    errors.append(f"{sub}/{fn}: text outside printable "
                                  f"ASCII: {text[:40]!r}")
    return errors


def _check_tolerance(kind: str, path: str) -> list:
    """A kind's results equal its reference bit for bit unless it states
    ``TOLERANCE = {"rtol": ..., "atol": ..., "why": "<one line>"}`` as a
    literal at the top of its file (read, not run): two numbers from 0,
    and a reason that is not empty."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "TOLERANCE"
                for t in node.targets):
            try:
                tol = ast.literal_eval(node.value)
            except ValueError:
                return [f"call kind {kind!r}: TOLERANCE is not a literal"]
            ok = (isinstance(tol, dict)
                  and set(tol) == {"rtol", "atol", "why"}
                  and all(isinstance(tol[k], (int, float))
                          and not isinstance(tol[k], bool) and tol[k] >= 0
                          for k in ("rtol", "atol")))
            if not ok:
                return [f"call kind {kind!r}: TOLERANCE has exactly rtol "
                        "and atol (numbers from 0) and why"]
            if not (one_line(tol["why"]) and tol["why"].strip()):
                return [f"call kind {kind!r}: TOLERANCE needs a why, one "
                        "line that says what a result within it still "
                        "proves"]
    return []


def _strings(node):
    if isinstance(node, str):
        yield node
    elif isinstance(node, dict):
        for k, v in node.items():
            yield k
            yield from _strings(v)
    elif isinstance(node, list):
        for v in node:
            yield from _strings(v)
