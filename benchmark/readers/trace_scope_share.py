"""Reader ``trace_scope_share``: the share, in %, of the selected points'
**device-busy seconds** that ran in ops of given scopes of the program, or
of a given pass.

The program wraps its step's parts in ``jax.named_scope("otpu_*")`` and
can say which instruction of its compiled step lies under which scopes and
in which pass (first forward, recomputed forward, backward, update):
``train.scopes_of_built_steps()``.  ``harness/scopes.py`` joins every
device op of the traced windows to that map by the instruction's and the
program's name and sums **self seconds** (a loop's seconds go to the ops
inside it, once) by (scope chain, pass).  This reader picks from that
table; every metric on it shares the one reduction of a run.  No trace, a
program that gives no map, or no selected point: nothing to read.

params: ``select``/``exclude``; then one of ``scopes`` (the rows with any
of them in their chain) and/or ``pass`` (``forward``, ``remat``,
``backward``, ``update``), or ``marked`` (``unnamed``: no scope in the
op's path or no entry in the map; ``mixed``: fusions that hold more than
one innermost scope or pass, booked to their root's).  The table goes to
``.bench_out/<cell>.step.scopes.json``, the maps beside it."""
from harness import scopes


def read(ctx, params):
    table = scopes.table_of(ctx, __file__, params)
    if table is None:
        return None
    return scopes.share(table, params)
