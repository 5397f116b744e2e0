"""Pallas kernels for the chunked gated delta rule: a pass's per-chunk
work and its chunk-to-chunk recurrence in one call, forward and backward.

A Gated DeltaNet layer's rule (``parallel/gdn._kernel_rule``, which
``gated_delta_net`` and ``gated_delta_chunked`` call) runs here where
Mosaic compiles (a TPU) and the shape has tiles (``supported``);
everywhere else it stays ``gated_delta_chunked``'s XLA form, which is
these kernels' oracle.  Both kernels walk the chunks in their
grid with the (dk, r x dv) float32 state of one key head's ``r`` value
heads in VMEM scratch, as the flash forward kernel keeps its softmax
state: no array of a chunk's products ((chunk, chunk) or (chunk, dv) a
value head) goes to HBM, and no step of the recurrence is a launch.

- ``rule_forward``: grid (batch, key head, group of chunks).  A step
  takes a group's rows of q and k (one key head's 128 columns of the
  (b, s, heads x 128) arrays as they leave the convolution, picked by
  the index maps: nothing is transposed in HBM) and of v (the key
  head's value heads, side by side), makes ``k k^T``, ``q k^T``, the
  decays, ``T = (I + L)^-1``, ``U``, ``W``, ``V' = U - W S``, the output
  and the state's update, writes o and, for a backward pass, the state
  that entered each chunk and the chunk's ``T`` (0.5 GB and 0.13 GB a
  layer at 16,384 positions and 32 value heads).
- ``rule_backward``: the same grid walked from the last group to the
  first with dS resident.  A step makes a chunk's ``U``, ``W``, ``V'``
  again from q, k, v, the running sum, beta and the saved state and
  ``T`` (the inverse is three fifths of the forward kernel's time: read
  back, not made again), and writes dq, dk, dv and the rows of dbeta
  and of the running sum's gradient.

``T``: the inverse of a unit lower-triangular (chunk, chunk) matrix is
made by products alone.  With D the inverse of the diagonal blocks of
width w and L' what lies outside them but inside the blocks of width
4 w, ``N = D L'`` has four block rows and ``N^4 = 0``, so the blocks of
width 4 w invert to ``(I - N)(I + N^2) D`` exactly: widths 1, 4, 16, 64,
ten products for a chunk of 64, a key head's two value heads' matrices
down one diagonal of a (128, 128) operand.  Each level solves
against the level below's exact inverse, as blocked forward substitution
does, so no power of L larger than a block's own is ever formed (the
plain series ``sum (-L)^k`` cancels terms many orders above its sum
where keys are alike).  Its gradient is ``-T^T ct T^T``, as
``gdn.unit_lower_inverse`` writes it out.

The L2 norms of q and k can run inside the kernels (``unit``), on rows
read where the convolution left them in its one [q | k | v] array: then
no slice, reshape or normalised copy of q, k and v exists in HBM in any
pass (4D views of them cost XLA two relayouts each, 45 ms of a 746 ms
step on the v5e, PR 52).

Every product is float32 at ``Precision.HIGHEST`` (Mosaic's
``contract_precision<fp32>``); running sums, exponentials and states
are float32.  The running sum of g inside a chunk and, backward, g's
gradient from the sum's are XLA's, outside the calls: (b, s, heads)
arrays, a thousandth of a chunk array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: positions a grid step takes: so many chunks' work is unrolled in one
#: step (4 chunks of 64), which amortises a step's overhead and lets the
#: scheduler fill one chunk's waits with another's products
STEP_ROWS = 256
VMEM_LIMIT = 64 << 20

_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def chunks_a_step(chunk: int):
    """The chunks a grid step takes (``STEP_ROWS`` positions), or None
    where ``chunk`` does not divide them."""
    return STEP_ROWS // chunk if STEP_ROWS % chunk == 0 else None


def supported(chunk: int, dk: int, dv: int, r: int, s: int) -> bool:
    """Whether the kernels have tiles for a rule of ``chunk`` positions a
    chunk, key and value heads ``dk`` and ``dv`` wide, ``r`` value heads
    a key head and ``s`` positions: heads of one tile's 128 lanes (a
    head's columns are a block of the (b, s, heads x 128) array), a chunk
    that is whole sublanes and divides a step's rows, and a state (dk, r
    x dv) that is a few tiles.  Any length: it is padded to whole steps."""
    return not refusal(chunk, dk, dv, r, s)


def refusal(chunk: int, dk: int, dv: int, r: int, s: int) -> str:
    """Why the kernels have no tiles for such a rule ("": they have): the
    clause of ``supported`` that fails first."""
    for what, width in (("key", dk), ("value", dv)):
        if width != LANES:
            return f"a {what} head is {width} wide, not {LANES}"
    if chunk % 8:
        return f"a chunk of {chunk} positions is no whole sublanes of 8"
    if chunks_a_step(chunk) is None:
        return f"chunk {chunk} does not divide a step's {STEP_ROWS} rows"
    if not 1 <= r <= 8:
        return f"{r} value heads a key head: the state is not 1 to 8 tiles"
    if s < 1:
        return "no position"
    return ""


def _at(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _unit_lower_inverse(low, c=None):
    """``(I + low)^-1`` of a strictly lower-triangular ``low``, by block
    widths 1, 4, 16, ... up to ``c`` (the module's text); ``low`` may
    hold several (c, c) matrices down its diagonal, which invert as one."""
    c = c or low.shape[0]
    row, col = _at(low.shape, 0), _at(low.shape, 1)
    eye = (row == col).astype(jnp.float32)
    inv, shift = eye, 0                   # blocks of width 1 << shift done
    while (1 << shift) < c:
        inside = (row >> shift + 2) == (col >> shift + 2)
        if shift:
            inside &= (row >> shift) != (col >> shift)
        n = jnp.where(inside, low, 0.0)
        if shift:
            n = _dot(inv, n)
        merged = _dot(eye - n, eye + _dot(n, n))
        inv = _dot(merged, inv) if shift else merged
        shift += 2
    return inv


def _unit_lower_inverses(lows):
    """``_unit_lower_inverse`` of each of the (c, c) ``lows``, as many at
    a time as fill a tile's lanes laid down one diagonal and inverted as
    one: two value heads' chunks of 64 are ten products of 128 x 128
    where they were twenty of 64 x 64 (the forward kernel 18.6 ms a layer
    for 21.4 on the v5e, PR 52)."""
    c = lows[0].shape[0]
    per = max(1, LANES // c)
    zero = jnp.zeros((c, c), jnp.float32)
    out = []
    for first in range(0, len(lows), per):
        some = lows[first:first + per]
        inv = _unit_lower_inverse(jnp.concatenate([jnp.concatenate(
            [low if i == j else zero for j in range(len(some))], axis=1)
            for i, low in enumerate(some)], axis=0), c)
        out += [inv[i * c:(i + 1) * c, i * c:(i + 1) * c]
                for i in range(len(some))]
    return out


def _column(block, head):
    """Column ``head`` of a (rows, heads) block as (rows, 1)."""
    return jnp.sum(jnp.where(_at(block.shape, 1) == head, block, 0.0),
                   axis=1, keepdims=True)


def _head_parts(cc_ref, cr_ref, b_ref, rows, head, j):
    """Of value head ``head``, the ``j``-th of its key head, in the
    chunk at ``rows``: the running sum as a column, beta as a column,
    and the decays ``exp(c_i - c_j)`` on and under the diagonal."""
    c_col = _column(cc_ref[rows, :], head)
    n = c_col.shape[0]
    decay = jnp.exp(jnp.where(_at((n, n), 0) >= _at((n, n), 1),
                              c_col - cr_ref[j:j + 1, rows], -jnp.inf))
    return c_col, _column(b_ref[rows, :], head), decay


def _unit_rows(x, eps):
    """(``x`` with its rows at unit length, the factor that made them so):
    ``layers.l2norm`` of the rows, ``x / sqrt(sum(x^2) + eps)``."""
    factor = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + eps)
    return x * factor, factor


def _fwd_kernel(chunk, group, r, save, unit, q_ref, k_ref, v_ref, cc_ref,
                cr_ref, b_ref, o_ref, *rest):
    """One group of chunks of one key head: the module's text."""
    s_ref = rest[-1]
    head0 = pl.program_id(1) * r
    dv = v_ref.shape[1] // r

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)

    strict = _at((chunk, chunk), 0) > _at((chunk, chunk), 1)
    for n in range(group):
        rows = slice(n * chunk, (n + 1) * chunk)
        q, k = q_ref[rows, :], k_ref[rows, :]
        if unit:
            q, k = (_unit_rows(q, unit[0])[0] * unit[1],
                    _unit_rows(k, unit[0])[0])
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        if save:
            rest[0][n] = s_ref[...]
        heads = [_head_parts(cc_ref, cr_ref, b_ref, rows, head0 + j, j)
                 for j in range(r)]
        solves = _unit_lower_inverses([
            jnp.where(strict, beta * kk * decay, 0.0)
            for _, beta, decay in heads])
        for j, ((c_col, beta, decay), solve) in enumerate(zip(heads, solves)):
            cols = slice(j * dv, (j + 1) * dv)
            if save:
                rest[1][n, :, j * chunk:(j + 1) * chunk] = solve
            grown = jnp.exp(c_col)
            wrote_read = _dot(solve, jnp.concatenate(
                [beta * v_ref[rows, cols], (beta * grown) * k], axis=1))
            state = s_ref[:, cols]
            new = wrote_read[:, :dv] - _dot(wrote_read[:, dv:], state)
            o_ref[rows, cols] = _dot(q, state) * grown \
                + _dot(qk * decay, new)
            last = c_col[chunk - 1:chunk, :]
            s_ref[:, cols] = state * jnp.exp(last) + _dot(
                k, new * jnp.exp(last - c_col), _TN)


def _bwd_kernel(chunk, group, r, unit, q_ref, k_ref, v_ref, cc_ref, cr_ref,
                b_ref, s_ref, t_ref, do_ref, dq_ref, dk_ref, dv_ref, dc_ref,
                db_ref, ds_ref):
    """One group of chunks of one key head, the last chunk first: each
    value head's parts made again from the saved state and inverse, then
    the gradient of every product the forward kernel makes, dS going
    from a chunk to the one before it in ``ds_ref``; with ``unit`` at
    last the gradient of the rows' norms."""
    head0 = pl.program_id(1) * r
    dv = v_ref.shape[1] // r

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, jnp.float32)

    square = (chunk, chunk)
    lower = _at(square, 0) >= _at(square, 1)
    strict = _at(square, 0) > _at(square, 1)
    rowsum = lambda x: jnp.sum(x, axis=1, keepdims=True)
    colsum = lambda x: jnp.sum(x, axis=0, keepdims=True)
    for n in reversed(range(group)):
        rows = slice(n * chunk, (n + 1) * chunk)
        q, k = q_ref[rows, :], k_ref[rows, :]
        if unit:
            (q_unit, q_factor), (k, k_factor) = (_unit_rows(q, unit[0]),
                                                 _unit_rows(k, unit[0]))
            q = q_unit * unit[1]
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        dq = jnp.zeros(q.shape, jnp.float32)
        dk = jnp.zeros(k.shape, jnp.float32)
        d_qk = jnp.zeros(square, jnp.float32)
        d_kk = jnp.zeros(square, jnp.float32)
        for j in range(r):
            cols = slice(j * dv, (j + 1) * dv)
            v, do = v_ref[rows, cols], do_ref[rows, cols]
            c_col, beta, decay = _head_parts(cc_ref, cr_ref, b_ref, rows,
                                             head0 + j, j)
            solve = t_ref[n, :, j * chunk:(j + 1) * chunk]
            grown = jnp.exp(c_col)
            rhs = jnp.concatenate([beta * v, (beta * grown) * k], axis=1)
            wrote_read = _dot(solve, rhs)
            state = s_ref[n, :, cols]
            read = wrote_read[:, dv:]
            new = wrote_read[:, :dv] - _dot(read, state)
            scores = qk * decay
            last = c_col[chunk - 1:chunk, :]
            through, to_end = jnp.exp(last), jnp.exp(last - c_col)
            left = new * to_end
            d_state = ds_ref[:, cols]

            # the state that leaves: through * state + k^T left
            dk += _dot(left, d_state, _NT)
            d_left = _dot(k, d_state)
            d_new = to_end * d_left + _dot(scores, do, _TN)
            d_to_end = rowsum(d_left * left)
            d_last = through * colsum(rowsum(state * d_state)) \
                + colsum(d_to_end)
            dc = -d_to_end
            # the output: (q state) grown + scores new
            d_qs = grown * do
            dc += rowsum(d_qs * _dot(q, state))
            dq += _dot(d_qs, state, _NT)
            d_enter = through * d_state + _dot(q, d_qs, _TN)
            d_scores = jnp.where(lower, _dot(do, new, _NT), 0.0)
            pull = d_scores * scores
            dc += rowsum(pull)
            dc_row = -colsum(pull)
            d_qk += d_scores * decay
            # new = wrote - read state, [wrote | read] = solve rhs
            d_enter -= _dot(read, d_new, _TN)
            d_wr = jnp.concatenate([d_new, -_dot(d_new, state, _NT)], axis=1)
            d_solve = _dot(d_wr, rhs, _NT)
            d_rhs = _dot(solve, d_wr, _TN)
            dv_ref[rows, cols] = beta * d_rhs[:, :dv]
            d_beta = rowsum(d_rhs[:, :dv] * v)
            dk += (beta * grown) * d_rhs[:, dv:]
            at_k = rowsum(d_rhs[:, dv:] * k)
            d_beta += at_k * grown
            dc += at_k * beta * grown
            # solve = (I + low)^-1, low = beta kk decay under the diagonal
            d_low = -_dot(_dot(solve, d_solve, _TN), solve, _NT)
            at_beta = jnp.where(strict, d_low * kk * decay, 0.0)
            d_beta += rowsum(at_beta)
            pull = beta * at_beta
            dc += rowsum(pull)
            dc_row -= colsum(pull)
            d_kk += jnp.where(strict, beta * d_low * decay, 0.0)
            dc += jnp.where(_at(dc.shape, 0) == chunk - 1, d_last, 0.0)
            ds_ref[:, cols] = d_enter
            # the two columns leave as rows: one transposition a chunk
            lane = _at((chunk, LANES), 1)
            as_rows = jnp.where(lane == 0, d_beta,
                                jnp.where(lane == 1, dc, 0.0)).T
            db_ref[j:j + 1, rows] = as_rows[0:1]
            dc_ref[j:j + 1, rows] = as_rows[1:2] + dc_row
        dq = dq + _dot(d_qk, k)
        dk = dk + _dot(d_qk, q, _TN) + _dot(d_kk, k) + _dot(d_kk, k, _TN)
        if unit:
            # x / |x|: the cotangent less its part along the unit row
            dq = (dq - q_unit * rowsum(dq * q_unit)) * (q_factor * unit[1])
            dk = (dk - k * rowsum(dk * k)) * k_factor
        dq_ref[rows, :] = dq
        dk_ref[rows, :] = dk


def _laid_out(q, k, v, g, beta, chunk, group, hk):
    """The kernels' operands from the rule's: the length padded with
    zeros to whole steps (k = 0 writes nothing, g = 0 leaves the state as
    it is), and the running sum of g inside each chunk by position (a
    column a head) and by head (a row a head)."""
    bt, s, hv = g.shape
    pad = -s % (chunk * group)
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                            for t in (q, k, v, g, beta))
    sp = s + pad
    cum = jnp.cumsum(g.reshape(bt, sp // chunk, chunk, hv), axis=2
                     ).reshape(bt, sp, hv)
    by_head = cum.transpose(0, 2, 1).reshape(bt, hk, hv // hk, sp)
    return q, k, v, cum, by_head, beta


def _specs(hk, hv, chunk, group, order, at=(0, 0, 0)):
    """Block specs of the operands the two kernels share, in
    ``_laid_out``'s order (q's, k's and v's heads from the lane blocks
    ``at``), and those of (a key head's columns of a (b, s, hk x 128)
    array, its value heads' of a (b, s, hv x 128) one, the saved states,
    the saved inverses, a (b, hk, r, s) array of rows); ``order`` maps
    the grid's third index to the group of chunks it takes."""
    r, rows = hv // hk, chunk * group
    cols = lambda width, first: pl.BlockSpec(
        (None, rows, width), lambda z, h, c: (z, order(c), first + h))
    col = pl.BlockSpec((None, rows, hv), lambda z, h, c: (z, order(c), 0))
    row = pl.BlockSpec((None, None, r, rows),
                       lambda z, h, c: (z, h, 0, order(c)))
    kept = lambda *tile: pl.BlockSpec(
        (None, None, group) + tile, lambda z, h, c: (z, h, order(c), 0, 0))
    return [cols(LANES, at[0]), cols(LANES, at[1]), cols(r * LANES, at[2]),
            col, row, col], (
        cols(LANES, 0), cols(r * LANES, 0), kept(LANES, r * LANES),
        kept(chunk, r * chunk), row)


def _call(kernel, name, operands, grid, in_specs, out_specs, out_shapes,
          state_shape, interpret):
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    return pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
                        for shape in out_shapes),
        grid=grid, in_specs=in_specs, out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM(state_shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name,
    )(*operands)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "hk", "at", "unit", "group", "states", "interpret"))
def rule_forward(q, k, v, g, beta, *, chunk: int, hk: int, at=(0, 0, 0),
                 unit=None, group=None, states: bool = False,
                 interpret: bool = False):
    """The gated delta rule's output o (bt, s, hv x 128) float32 for
    ``hk`` key heads and the ``hv`` value heads of g and beta (bt, s, hv),
    all float32, in chunks of ``chunk`` positions, ``group`` of them a
    grid step (``chunks_a_step``).  q, k and v are arrays of (bt, s, .)
    with a head's 128 columns side by side: key head h's q is the lane
    block ``at[0] + h`` of ``q``, its k block ``at[1] + h`` of ``k``, its
    ``hv / hk`` value heads block ``at[2] + h`` (of that many heads'
    width) of ``v``, so one array [q | k | v] given three times is read
    where it lies.  With ``unit`` = (eps, scale) q and k come as the
    convolution left them and the kernel puts their rows at unit length
    (``layers.l2norm``) and q's times ``scale``.  With ``states`` also
    what ``rule_backward`` reads, a pair: the state that entered each
    chunk, (bt, hk, chunks, 128, r x 128), and each chunk's ``T`` with a
    key head's value heads side by side, (bt, hk, chunks, chunk, r x
    chunk) (the chunks of the padded length)."""
    bt, s, hv = g.shape
    r = hv // hk
    group = group or chunks_a_step(chunk)
    operands = _laid_out(q, k, v, g, beta, chunk, group, hk)
    sp = operands[3].shape[1]
    in_specs, (_, val, state, solve, _) = _specs(hk, hv, chunk, group,
                                                 lambda c: c, at)
    shapes = [(bt, sp, hv * LANES)]
    if states:
        shapes += [(bt, hk, sp // chunk, LANES, r * LANES),
                   (bt, hk, sp // chunk, chunk, r * chunk)]
    out = _call(
        functools.partial(_fwd_kernel, chunk, group, r, states, unit),
        "otpu_gdn_rule_fwd", operands, (bt, hk, sp // (chunk * group)),
        in_specs, [val, state, solve][:len(shapes)], shapes,
        (LANES, r * LANES), interpret)
    return (out[0][:, :s], out[1:]) if states else out[0][:, :s]


@functools.partial(jax.jit, static_argnames=(
    "chunk", "hk", "at", "unit", "group", "interpret"))
def rule_backward(q, k, v, g, beta, kept, do, *, chunk: int, hk: int,
                  at=(0, 0, 0), unit=None, group=None,
                  interpret: bool = False):
    """(dq, dk (bt, s, hk x 128), dv (bt, s, hv x 128), dg, dbeta) of
    ``rule_forward``'s o for its cotangent ``do`` (bt, s, hv x 128), from
    the rule's operands as ``rule_forward`` took them and what the
    forward kernel ``kept`` of each chunk (``rule_forward(...,
    states=True)``: the state that entered and ``T``).  With ``unit`` dq
    and dk are those of q and k as they came."""
    bt, s, hv = g.shape
    r = hv // hk
    group = group or chunks_a_step(chunk)
    operands = _laid_out(q, k, v, g, beta, chunk, group, hk)
    sp = operands[3].shape[1]
    steps = sp // (chunk * group)
    do = jnp.pad(do.astype(jnp.float32), ((0, 0), (0, sp - s), (0, 0)))
    in_specs, (key, val, state, solve, row) = _specs(
        hk, hv, chunk, group, lambda c: steps - 1 - c, at)
    dq, dk, dv, dc, dbeta = _call(
        functools.partial(_bwd_kernel, chunk, group, r, unit),
        "otpu_gdn_rule_bwd", operands + tuple(kept) + (do,),
        (bt, hk, steps), in_specs + [state, solve, val],
        [key, key, val, row, row],
        [(bt, sp, hk * LANES)] * 2 + [(bt, sp, hv * LANES)]
        + [(bt, hk, r, sp)] * 2, (LANES, r * LANES), interpret)
    by_position = lambda t: t.reshape(bt, hv, sp).transpose(0, 2, 1)
    # g's gradient from its running sum's: the sum from a position to its
    # chunk's end
    dc = by_position(dc).reshape(bt, sp // chunk, chunk, hv)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dc, 2), axis=2), 2).reshape(bt, sp, hv)
    return (dq[:, :s], dk[:, :s], dv[:, :s], dg[:, :s],
            by_position(dbeta)[:, :s])
