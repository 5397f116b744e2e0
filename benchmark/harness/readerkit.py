"""What the readers share: choosing rows of the per-point table."""
from __future__ import annotations


def _matches(row: dict, want: dict) -> bool:
    return all(row.get(k) in (v if isinstance(v, list) else [v])
               for k, v in want.items())


def select(rows: list, params: dict) -> list:
    """The rows that match every key of ``params['select']`` (a value or
    a list of values) and not every key of ``params['exclude']``."""
    chosen = [r for r in rows if _matches(r, params.get("select", {}))]
    if params.get("exclude"):
        chosen = [r for r in chosen if not _matches(r, params["exclude"])]
    return chosen
