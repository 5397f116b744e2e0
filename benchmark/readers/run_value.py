"""Reader ``run_value``: one fact of the run the harness took itself:
``setup_s``, ``check_s`` and ``init_s`` from its clock, ``compile_s``
(seconds in the backend compiler during set-up) from JAX's own compile
events.

params: ``key``."""


def read(ctx, params):
    return ctx["run"].get(params["key"])
