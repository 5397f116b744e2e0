"""Reader ``program_counter``: one of the program's own SPC counters
(``ompi_tpu.runtime.spc``), read in process before ``finalize()``, over
the whole run since ``init()``.

params: ``name``; ``over`` (optional: another counter to divide by);
``scale`` (optional factor: 100 for a share in %, 1e-6 for seconds from
microseconds); ``until`` (optional, ``"setup"``: the counters as they stood
when set-up ended, ``harness/counters.AT_SETUP``, so that a metric that
moves ``setup_s`` counts nothing of the harness's own check, which builds
its reference programs after it; read before set-up has ended, the
counters as they stand).  A program that does not have the counter, or an
``over`` that reads 0: nothing to read."""


def read(ctx, params):
    from ompi_tpu.runtime import spc

    from harness import counters

    have = spc.counters()
    if params.get("until") == "setup" and counters.AT_SETUP:
        have = counters.AT_SETUP
    if params["name"] not in have:
        return None
    value = float(have[params["name"]])
    if params.get("over"):
        base = float(have.get(params["over"], 0))
        if base <= 0:
            return None
        value /= base
    return value * params.get("scale", 1)
