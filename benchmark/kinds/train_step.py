"""Call kind ``train_step``: one optimiser step of a public model through
the program's normal entry, ``parallel/train.build_train_step(mesh, spec,
model=...)``.  A call is a **step**: forward, loss, backward, the
gradient ``psum`` over ``dp`` and AdamW, one jitted program; the state
(float32 parameters, AdamW's two moments, the step count) is held here
and donated into every step, as ``pallreduce`` holds its request.  The
step's ``psum`` never passes a ``world.*_array`` slot, so SPC
``device_collectives`` does not move.

The point names its configuration file (``configs/<config>.json``, beside
this directory): the widths, ``layers_here`` and the ``train`` group are
read from it and nothing about the model is stated here, so a rehearsal
at tiny widths only writes another file.  ``bind`` is handed no seed: the
seed of the parameters and of the token order is taken from the first
words of the pool's first entry, which is drawn from the run's seed.

A batch: the generated ``int32`` bit patterns (``sequences`` x
``seq_len`` + 1) become token ids by a Zipf law of exponent 1 over the
vocabulary, in an order permuted from the seed (natural text's unigram
skew, which makes the first layer's routing uneven), and are cut into
inputs and labels (``prepare``).  **Every timed step takes the next batch
of the pool**, whatever entry the harness hands over: with k = 1 that is
the pool's first every time (``call``), and a trainer that met one batch
a hundred times over would be timed, and checked, in a state no training
job is in.  The checked call takes the batch it is given.

**How one step is compared with the reference from the same
parameters.**  ``inputs_of`` runs before the checked call, and there
copies the current parameters to the host (2.5 GB at the published
widths; a copy on the device would not fit beside the step's own 12.4
GB).  ``reference`` puts them back on the device once the step is done
and runs the benchmark's own plain float32 model (``harness/olmoekit``)
on the same batch.  A step's result that carried what the reference
needs would have to carry the parameters themselves, so the copy is the
smaller way.  The step returns raw statistics (``parallel/train.py``'s
``aux``); a timed call hands them back as they are, and the checked
call, the one that follows ``inputs_of``, reads them on the host and
puts them in the units of ``olmoekit.compared`` and
``olmoekit.precision_got``, because the harness applies one tolerance
to everything.

**The reference takes the step's routing as given** (the experts every
token chose come back in the step's result): bfloat16 turns a near-tie
of the top 8 another way, and where a whole batch shares one, as every
late position of a freshly drawn model does, for thousands of tokens at
once, so two right computations differ by whole experts' outputs on
those rows.  With the choice given everything else is compared element
by element, and the choice itself by its **regret** under the
reference's own probabilities (``olmoekit.loss_parts``).

Compared (``OUTPUTS``), **against the whole float32 model**: the loss and
its three parts as weighted into it; the share of the step's slots every
expert received; the head's logsumexp and label logit averaged over
quarters of the rows; the routing's regret; and, for the leaves of
``CHECKED``, the gradient's RMS (as log10 over 4) and 64 entries in units
of 128 times that RMS.  bfloat16 matmul inputs move each of these by about a
thousandth of its unit, so this half tells the stated precision from
bfloat16 throughout and a right model from a wrong one, and cannot tell a
float32 router or loss from a bfloat16 one.  That is the other half's:
**against each float32 part recomputed from the step's own inputs to it**
(``olmoekit.precision_want``), at 16 rows: the router's logits from the
rows the router read, their logsumexp and chosen probabilities from the
step's own logits, the head's logsumexp and label logit from the rows
the head read (inputs rounded to bfloat16, as the configuration states,
every product exact).  float32 meets these within a twentieth of the
tolerance; a bfloat16 router, softmax or loss lies outside it
(``tools/train_check.py`` prints both).

``CHECKED``: the head, the final norm's gain, the gain in front of the
router and the experts, the experts' down projection, the router, and of
attention the value, output and key projections and the gain in front of
them: between them their gradients cross the loss, the head, the combine,
the grouped matmuls' backward passes, the dispatch, the router, and
attention's own backward pass on both sides of its softmax.  The other six
leaves (the embedding, ``wq``, the two QK gains, ``gate``, ``up``: the
last two 0.5 GB of gradient each in the reference) are read by
``tools/train_check.py`` on fresh parameters, with the parameters after an
update and the controls.
"""
import json
import os

import numpy as np

# the parent of the PR that brought this kind has no model path: a run
# there must stop here, before any input is drawn
from ompi_tpu.parallel.train import (build_train_step, init_model_params,
                                     load_model_config, record_step_stats)

from harness import olmoekit

TOLERANCE = {"rtol": 0.000375, "atol": 0.005, "why": "bfloat16 matmul inputs against a float32 reference: four times the widest deviation read on the chip; the all-bfloat16 reference and a bfloat16 router, softmax or loss lie outside it (PERF.md 2)"}
ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 0
OUTPUTS = ("losses", "load_share", "row_means", "route_regret",
           "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_lse", "router_weights", "head_rows")
CHECKED = ("ln2", "final_norm", "head", "down", "router", "wv", "wo", "wk",
           "ln1")
_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_RUN: dict = {}         # this point's seed and bound trainer (one module
#                         is loaded a point)


def config_path(point) -> str:
    return os.path.join(_BENCH_DIR, "configs", point["config"] + ".json")


def _mesh(env):
    from ompi_tpu.parallel.mesh import MeshSpec, make_mesh

    if "mesh" not in _RUN:
        _RUN["mesh"] = make_mesh(env.devices, MeshSpec(dp=len(env.devices)))
    return _RUN["mesh"]


def input_shape(point, n):
    return (point["sequences"], point["seq_len"] + 1)


def input_sharding(env):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(_mesh(env)[0], P("dp", None))


def prepare(env, point, bits):
    """(inputs, labels) of one batch from its generated bit patterns."""
    import jax

    if "seed" not in _RUN:
        first = np.asarray(bits[0, :2]).astype(np.uint32)
        _RUN["seed"] = int(first[0]) ^ (int(first[1]) << 1)
        vocab = olmoekit.load_config(config_path(point))["vocab_size"]
        sharding = input_sharding(env)
        cdf = jax.device_put(olmoekit.zipf_cdf(vocab))
        order = jax.device_put(olmoekit.rank_order(vocab, _RUN["seed"]))
        cut = jax.jit(lambda b: (lambda t: (t[:, :-1], t[:, 1:]))(
            olmoekit.tokens_of(b, cdf, order)),
            out_shardings=(sharding, sharding))
        _RUN["cut"] = cut
    batch = _RUN["cut"](bits)
    _RUN.setdefault("pool", []).append(batch)
    return batch


def bind(env, point, first):
    cfg = load_model_config(config_path(point))
    if (cfg.micro_batch, cfg.seq_len) != (point["sequences"],
                                          point["seq_len"]):
        raise ValueError(f"{point['name']}: the point's batch is not the "
                         "configuration's micro_batch x seq_len")
    mesh, spec = _mesh(env)
    step, place = build_train_step(mesh, spec, model=cfg)
    state, _, _ = place(init_model_params(cfg, _RUN["seed"] & 0x7FFFFFFF),
                        *first)
    held = {"state": state, "aux": None, "next": 0}
    _RUN.update(held=held, cfg=olmoekit.load_config(config_path(point)))
    print("config " + json.dumps({
        k: getattr(cfg, k) for k in (
            "hidden_size", "num_attention_heads", "num_experts",
            "num_experts_per_tok", "intermediate_size", "vocab_size",
            "seq_len", "micro_batch", "layers_here", "compute_dtype")}),
        flush=True)

    def call(batch):
        import jax

        checked = _RUN.pop("checking", False)
        if not checked:
            # a window of one call is always handed the pool's first
            # entry (``harness/protocol.window`` starts its cycle anew
            # every window, and a step is a window): the trainer walks
            # the pool itself, so that every step takes the next batch
            pool = _RUN["pool"]
            batch = pool[held["next"] % len(pool)]
            held["next"] += 1
        held["state"], held["aux"] = step(held["state"], *batch)
        if not checked:
            return jax.tree.leaves(held["aux"])
        aux = held["aux"] = jax.device_get(held["aux"])
        got = held["got"] = {
            **olmoekit.compared(olmoekit.step_stats(aux), _RUN["cfg"],
                                CHECKED),
            **olmoekit.precision_got(aux, _RUN["cfg"])}
        return [got[k] for k in OUTPUTS + PRECISION]

    return call, 0


def inputs_of(batch):
    """The batch and a host copy of the parameters the checked step will
    start from; the call that follows is the checked one.  Also where
    the last timed step's expert loads are read into SPC
    ``moe_max_expert_load``: outside every window."""
    import jax

    held = _RUN["held"]
    if held["aux"] is not None:
        record_step_stats(held["aux"])
    params = jax.device_get(held["state"][0])
    _RUN["checking"] = True
    return list(batch) + [olmoekit.leaf_of(params, n)
                          for n in olmoekit.LEAVES]


def reference(point, n, xs):
    import jax

    held, cfg = _RUN["held"], _RUN["cfg"]
    aux, got = held["aux"], held["got"]             # the checked step's
    record_step_stats(aux)
    tokens, labels = (jax.device_put(a) for a in xs[:2])
    leaves = dict(zip(olmoekit.LEAVES, xs[2:]))
    params = jax.device_put({
        "embed": leaves["embed"], "final_norm": leaves["final_norm"],
        "head": leaves["head"],
        "layers": {k: leaves[k] for k in olmoekit.LAYER_LEAVES}})
    out = jax.device_get({k: v for k, v in olmoekit.reference_step(
        params, tokens, labels, cfg, CHECKED, routed=aux["experts"]
    ).items() if k != "grads"})
    want = {**olmoekit.compared(out, cfg, CHECKED),
            **olmoekit.precision_want(aux, leaves["router"],
                                      params["head"], xs[1], cfg)}
    units = {}
    for k in OUTPUTS + PRECISION:
        u = np.abs(np.float64(got[k]) - want[k]) / (
            TOLERANCE["atol"] + TOLERANCE["rtol"] * np.abs(want[k]))
        if k.startswith("grad_"):       # by leaf
            units[k] = {n: round(float(np.max(u[i])), 3)
                        for i, n in enumerate(CHECKED)}
        else:
            units[k] = round(float(np.max(u)), 3)
    print(f"check {point['name']}: widest deviation in units of the "
          f"tolerance: {json.dumps(units)}", flush=True)
    return [want[k] for k in OUTPUTS + PRECISION]


def bus_bytes(point, n):
    return 0.0


def moved_bytes(point, n):
    return 0
