"""Call kind ``train_step_kit``: one optimiser step of a public model on
**one chip's share of a deployment**, through the program's normal entry,
``parallel/train.build_train_step(mesh, spec, model=...)``, with
**everything about the model read from the kit** that the point's
configuration file names (``harness/<kit>.py``).  The kind
``train_step_share`` is this for one shape of model: its file names
JoyAI's checked leaves, its next-next-token module's router and its
second bias row, so a model without such a module cannot pass through it
(PR 42; ``benchmark/README.md``).  Here the kit gives the leaves
(``leaves(cfg)``), the checked ones (``checked(cfg)``, and of those
``probed(cfg)`` entry by entry), what is compared
(``OUTPUTS``, ``PRECISION``) and in which units (``compared``,
``precision_got``, ``precision_want``), and the reference
(``reference_step``); the mesh, the sharding, the pool walk, the held and
donated state and the batch's form are ``train_step``'s and
``train_step_share``'s as they are.  A call is a **step**; its ``psum``
never passes a ``world.*_array`` slot, so SPC ``device_collectives`` does
not move.

A batch: ``sequences`` x (``seq_len`` + 2) generated bit patterns become
token ids by a Zipf law of exponent 1 over the rank's **slice** of the
vocabulary, in an order permuted from the seed, and are cut into inputs
(all but the last two) and labels (all but the first; a model without a
next-next-token head reads all but the last of those).  Every timed step
takes the next batch of the pool; the checked call takes the batch it is
given.

**How one step is compared with the reference from the same parameters**
(``train_step``'s way): ``inputs_of`` copies the current parameters and
balancing biases to the host before the checked call; ``reference`` puts
them back once the step is done (AdamW's moments wait on the host
meanwhile: the chip cannot hold both) and runs the kit's float32 model,
given the same share, on the same batch **under the step's own routing**
(bfloat16 turns a near-tie of the top k another way for thousands of
tokens at once; the choice itself is compared by its regret under the
reference's own scores plus bias).  Compared: the kit's ``OUTPUTS``
against the whole float32 model, and its ``PRECISION`` against each
float32 part recomputed from the step's own inputs to it.
``tools/kit_check.py`` reads every leaf, the parameters after the update
and the controls.
"""
import importlib
import json
import os

import numpy as np

# a program that cannot load the configuration (the parent of the PR that
# brought it) stops at bind, before anything is timed
from ompi_tpu.parallel.train import (build_train_step, init_model_params,
                                     load_model_config, record_step_stats)

from harness import manifest, protocol

TOLERANCE = {"rtol": 0.000375, "atol": 0.005, "why": "bfloat16 matmul inputs against a float32 reference, the program's widest deviation on the chip lies inside; a bfloat16 reference and each wrong or bfloat16 part lie outside (PERF.md 2)"}
ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 0
base = protocol.load_module("kinds", "train_step", os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_RUN = base._RUN            # this point's seed, pool and bound trainer
config_path, input_sharding = base.config_path, base.input_sharding
bus_bytes, moved_bytes = base.bus_bytes, base.moved_bytes


def start_of(point) -> dict:
    """The configuration file's ``start`` group: how a run's parameters
    are drawn where that is not the program's default for the file
    (``embed_init_std``: the embedding's rows alone drawn wide, so that
    the routers choose by the token; the file's ``assumed`` says why).
    Handed to the program's loader as overrides, so it moves initial
    values and nothing of the compiled step."""
    return manifest.load_json(config_path(point)).get("start", {})


def kit_of(point):
    """(the kit's module, the configuration as the kit reads it)."""
    path = config_path(point)
    kit = importlib.import_module(
        "harness." + manifest.load_json(path)["kit"])
    return kit, kit.load_config(path)


def input_shape(point, n):
    return (point["sequences"], point["seq_len"] + 2)


def prepare(env, point, bits):
    """(inputs, labels) of one batch from its generated bit patterns."""
    import jax

    if "seed" not in _RUN:
        first = np.asarray(bits[0, :2]).astype(np.uint32)
        _RUN["seed"] = int(first[0]) ^ (int(first[1]) << 1)
        kit, cfg = kit_of(point)
        sharding = input_sharding(env)
        cdf = jax.device_put(kit.zipf_cdf(cfg["vocab_here"]))
        order = jax.device_put(kit.rank_order(cfg["vocab_here"],
                                              _RUN["seed"]))
        _RUN["cut"] = jax.jit(
            lambda b: (lambda t: (t[:, :-2], t[:, 1:]))(
                kit.tokens_of(b, cdf, order)),
            out_shardings=(sharding, sharding))
    batch = _RUN["cut"](bits)
    _RUN.setdefault("pool", []).append(batch)
    return batch


def bind(env, point, first):
    cfg = load_model_config(config_path(point), **start_of(point))
    if (cfg.micro_batch, cfg.seq_len) != (point["sequences"],
                                          point["seq_len"]):
        raise ValueError(f"{point['name']}: the point's batch is not the "
                         "configuration's micro_batch x seq_len")
    mesh, spec = base._mesh(env)
    step, place = build_train_step(mesh, spec, model=cfg)
    state, _, _ = place(init_model_params(cfg, _RUN["seed"] & 0x7FFFFFFF),
                        *first)
    held = {"state": state, "aux": None, "next": 0}
    kit, kcfg = kit_of(point)
    _RUN.update(held=held, kit=kit, cfg=kcfg, checked=kit.checked(kcfg),
                probed=kit.probed(kcfg), names=kit.OUTPUTS + kit.PRECISION)
    print("config " + json.dumps({
        k: getattr(cfg, k) for k in (
            "hidden_size", "pattern_here", "n_heads_here", "n_kv_heads_here",
            "n_mamba_heads_here", "n_groups_here", "num_experts",
            "n_experts_here", "first_expert_here", "num_experts_per_tok",
            "expert_width", "vocab_rows", "seq_len", "micro_batch",
            "n_mtp_here", "compute_dtype")}), flush=True)

    def call(batch):
        import jax

        checking = _RUN.pop("checking", False)
        if not checking:
            # the trainer walks the pool itself (kinds/train_step.py)
            pool = _RUN["pool"]
            batch = pool[held["next"] % len(pool)]
            held["next"] += 1
        held["state"], held["aux"] = step(held["state"], *batch)
        if not checking:
            return jax.tree.leaves(held["aux"])
        aux = held["aux"] = jax.device_get(held["aux"])
        got = held["got"] = {
            **kit.compared(kit.step_stats(aux, jax.device_get(
                held["state"][4]), kcfg), kcfg, _RUN["checked"]),
            **kit.precision_got(aux, kcfg)}
        return [got[k] for k in _RUN["names"]]

    return call, 0


def inputs_of(batch):
    """The batch and a host copy of the parameters and biases the checked
    step will start from; the call that follows is the checked one.  Also
    where the last timed step's expert loads are read into SPC: outside
    every window."""
    import jax

    held, kit, cfg = _RUN["held"], _RUN["kit"], _RUN["cfg"]
    if held["aux"] is not None:
        record_step_stats(held["aux"])
    params, bias = jax.device_get((held["state"][0], held["state"][4]))
    _RUN["checking"] = True
    return list(batch) + [kit.leaf_of(params, n) for n in kit.leaves(cfg)] \
        + [bias["layers"]]


def units_of(got: dict, want: dict) -> dict:
    """The widest deviation of each compared quantity in units of the
    tolerance (a gradient's by leaf)."""
    units = {}
    for k in _RUN["names"]:
        u = np.abs(np.float64(got[k]) - want[k]) / (
            TOLERANCE["atol"] + TOLERANCE["rtol"] * np.abs(want[k]))
        if k.startswith("grad_"):
            names = _RUN["probed" if k == "grad_probe" else "checked"]
            units[k] = {n: round(float(np.max(u[i])), 3)
                        for i, n in enumerate(names)}
        else:
            units[k] = round(float(np.max(u)), 3)
    return units


def reference(point, n, xs):
    import jax

    held, kit, cfg = _RUN["held"], _RUN["kit"], _RUN["cfg"]
    aux, got = held["aux"], held["got"]             # the checked step's
    record_step_stats(aux)
    # AdamW's two moments are idle until the next step: they wait on the
    # host while the reference runs, so that its parameters, its checked
    # gradients and its temporaries fit beside the trainer's parameters
    params_now, *moments, count, bias_now = held["state"]
    where = jax.tree.map(lambda a: a.sharding, moments)
    on_host = jax.device_get(moments)
    for a in jax.tree.leaves(moments):
        a.delete()
    del moments
    tokens, labels = (jax.device_put(a) for a in xs[:2])
    by_name = dict(zip(kit.leaves(cfg), xs[2:-1]))
    bias = {"layers": xs[-1]}
    params = jax.device_put(kit.tree_of(by_name))
    out = jax.device_get({k: v for k, v in kit.reference_step(
        params, tokens, labels, cfg, jax.device_put(bias), _RUN["checked"],
        routed=aux["experts"]).items() if k != "grads"})
    want = {**kit.compared(out, cfg, _RUN["checked"]),
            **kit.precision_want(aux, by_name, bias["layers"],
                                 params["head"], xs[1], cfg)}
    held["state"] = (params_now, *jax.device_put(on_host, where), count,
                     bias_now)
    if _RUN.get("keep_last"):       # tools/kit_check.py reads controls
        held["last"] = dict(params=params, tokens=tokens, labels=labels,
                            bias=bias, by_name=by_name, want=want, out=out)
    print(f"check {point['name']}: widest deviation in units of the "
          f"tolerance: {json.dumps(units_of(got, want))}", flush=True)
    return [want[k] for k in _RUN["names"]]
