"""Call kind ``train_step_share``: one optimiser step of a public model on
**one chip's share of an expert-parallel deployment**, through the
program's normal entry, ``parallel/train.build_train_step(mesh, spec,
model=...)``: the kind ``train_step`` (whose mesh, sharding, pool walk and
held, donated state this one takes as they are) for a model whose rank
holds some of the routed experts and a slice of the vocabulary, chooses
under a balancing bias that the step itself moves, and has a
next-next-token head.  A call is a **step**; its ``psum`` never passes a
``world.*_array`` slot, so SPC ``device_collectives`` does not move.

The point names its configuration file; the file names its **kit**
(``harness/<kit>.py``: the plain reference, its units and its FLOP), so
nothing about the model is stated here and the next model of this shape
brings a file and a kit.  A batch: ``sequences`` x (``seq_len`` + 2)
generated bit patterns become token ids by a Zipf law of exponent 1 over
the rank's **slice** of the vocabulary, in an order permuted from the
seed, and are cut into inputs (all but the last two) and labels (all but
the first: the next token and, one further, the one after).  Every timed
step takes the next batch of the pool; the checked call takes the batch
it is given.

**How one step is compared with the reference from the same parameters**
(``train_step``'s way): ``inputs_of`` copies the current parameters and
balancing biases to the host (2.7 GB) before the checked call;
``reference`` puts them back once the step is done (AdamW's moments wait
on the host meanwhile: the chip cannot hold both) and runs the kit's
float32 model, given the same share, on the same batch **under the
step's own routing** (bfloat16 turns a near-tie of the top 8 another way
for thousands of tokens at once; the choice itself is compared by its
regret under the reference's own scores plus bias).

Compared (``OUTPUTS``), against the whole float32 model: the loss and
both cross-entropies; the share of a layer's slots each of all 256
experts received and the held experts' together; both heads' logsumexp
and label logit averaged over quarters of the rows; the routing's
regret; the biases after the update in units of gamma; and for the
leaves of ``CHECKED`` the gradient's RMS (log10 over 4) and 64 entries in
units of 128 RMS.  And (``PRECISION``) against each float32 part
recomputed from the step's own inputs to it, at 16 rows: the routers'
logits, sigmoid scores and chosen weights, both heads' rows.

``CHECKED`` crosses, between its leaves' gradients: both uses of the
head (``head``, ``final_norm``, ``mtp.norm``), the module's projection
and its two norms' sides (``mtp.proj``, ``mtp.hnorm``), the combine, the
grouped matmuls and the dispatch (``down``, ``gate``, ``ln2``), the
router through the weights (``router``), the shared expert
(``shared_down``), and latent attention on both sides of its softmax
(``wo``, ``wkv_b`` after it; ``wq_b``, ``wkv_a`` before) and of its two
inner norms (``kv_a_norm``, ``q_a_norm``, ``wq_a``), in a sparse layer,
in the dense one (``dense.wkv_b``, ``dense.down``) and in the module
(``mtp.wq_a``).  ``tools/share_check.py`` reads every leaf, the
parameters after the update and the controls.
"""
import importlib
import json
import os

import numpy as np

# the parent of the PR that brought this kind cannot load the
# configuration (its ModelConfig knows one kind of layer): a run there
# stops at bind, before anything is timed
from ompi_tpu.parallel.train import (build_train_step, init_model_params,
                                     load_model_config, record_step_stats)

from harness import manifest, protocol

TOLERANCE = {"rtol": 0.000375, "atol": 0.005, "why": "bfloat16 matmul inputs against a float32 reference, in the kit's units: the program's widest deviation on the chip lies inside it; the bfloat16 reference and each wrong router lie outside (PERF.md 2)"}
ELEMENTWISE_LAST_AXIS = False
COLLECTIVES_PER_CALL = 0
OUTPUTS = ("losses", "load_share", "local_share", "row_means",
           "route_regret", "bias", "grad_log_rms", "grad_probe")
PRECISION = ("router_logits", "router_scores", "router_weights",
             "head_rows")
CHECKED = ("head", "final_norm", "mtp.norm", "mtp.proj", "mtp.hnorm",
           "down", "gate", "ln2", "router", "shared_down", "wo", "wkv_b",
           "wq_b", "wkv_a", "kv_a_norm", "q_a_norm", "wq_a", "dense.wkv_b",
           "dense.down", "mtp.wq_a")
base = protocol.load_module("kinds", "train_step", os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_RUN = base._RUN            # this point's seed, pool and bound trainer
config_path, input_sharding = base.config_path, base.input_sharding
bus_bytes, moved_bytes = base.bus_bytes, base.moved_bytes


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
    cfg = load_model_config(config_path(point))
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
    _RUN.update(held=held, kit=kit, cfg=kcfg)
    print("config " + json.dumps({
        k: getattr(cfg, k) for k in (
            "hidden_size", "num_attention_heads", "kv_lora_rank",
            "q_lora_rank", "num_experts", "n_experts_here",
            "first_expert_here", "num_experts_per_tok", "expert_width",
            "vocab_rows", "seq_len", "micro_batch", "n_dense_here",
            "n_sparse_here", "num_nextn_predict_layers",
            "compute_dtype")}), flush=True)

    def call(batch):
        import jax

        checked = _RUN.pop("checking", False)
        if not checked:
            # the trainer walks the pool itself (kinds/train_step.py)
            pool = _RUN["pool"]
            batch = pool[held["next"] % len(pool)]
            held["next"] += 1
        held["state"], held["aux"] = step(held["state"], *batch)
        if not checked:
            return jax.tree.leaves(held["aux"])
        aux = held["aux"] = jax.device_get(held["aux"])
        got = held["got"] = {
            **kit.compared(kit.step_stats(aux, jax.device_get(
                held["state"][4])), kcfg, CHECKED),
            **kit.precision_got(aux, kcfg)}
        return [got[k] for k in OUTPUTS + PRECISION]

    return call, 0


def inputs_of(batch):
    """The batch and a host copy of the parameters and biases the checked
    step will start from; the call that follows is the checked one.  Also
    where the last timed step's expert loads are read into SPC: outside
    every window."""
    import jax

    held, kit = _RUN["held"], _RUN["kit"]
    if held["aux"] is not None:
        record_step_stats(held["aux"])
    params, bias = jax.device_get((held["state"][0], held["state"][4]))
    _RUN["checking"] = True
    return list(batch) + [kit.leaf_of(params, n) for n in kit.LEAVES] \
        + [bias["layers"], bias["mtp"]]


def units_of(got: dict, want: dict) -> dict:
    """The widest deviation of each compared quantity in units of the
    tolerance (a gradient's by leaf)."""
    units = {}
    for k in OUTPUTS + PRECISION:
        u = np.abs(np.float64(got[k]) - want[k]) / (
            TOLERANCE["atol"] + TOLERANCE["rtol"] * np.abs(want[k]))
        if k.startswith("grad_"):
            units[k] = {n: round(float(np.max(u[i])), 3)
                        for i, n in enumerate(CHECKED)}
        else:
            units[k] = round(float(np.max(u)), 3)
    return units


def reference(point, n, xs):
    import jax

    held, kit, cfg = _RUN["held"], _RUN["kit"], _RUN["cfg"]
    aux, got = held["aux"], held["got"]             # the checked step's
    record_step_stats(aux)
    # AdamW's two moments (5.4 GB) are idle until the next step: they
    # wait on the host while the reference runs, so that its parameters
    # (2.7 GB) and its temporaries (5.8 GB by the compiler's account)
    # fit beside the trainer's own parameters
    params_now, *moments, count, bias_now = held["state"]
    where = jax.tree.map(lambda a: a.sharding, moments)
    on_host = jax.device_get(moments)
    for a in jax.tree.leaves(moments):
        a.delete()
    del moments
    tokens, labels = (jax.device_put(a) for a in xs[:2])
    leaves = dict(zip(kit.LEAVES, xs[2:-2]))
    bias = {"layers": xs[-2], "mtp": xs[-1]}
    params = jax.device_put(kit.tree_of(leaves))
    out = jax.device_get({k: v for k, v in kit.reference_step(
        params, tokens, labels, cfg, jax.device_put(bias), CHECKED,
        routed=aux["experts"]).items() if k != "grads"})
    routers = np.concatenate([leaves["router"], leaves["mtp.router"][None]])
    want = {**kit.compared(out, cfg, CHECKED),
            **kit.precision_want(aux, routers, np.concatenate(
                [bias["layers"], bias["mtp"]]), params["head"], xs[1], cfg)}
    held["state"] = (params_now, *jax.device_put(on_host, where), count,
                     bias_now)
    if _RUN.get("keep_last"):       # tools/share_check.py reads controls
        held["last"] = dict(params=params, tokens=tokens, labels=labels,
                            bias=bias, routers=routers, want=want)
    print(f"check {point['name']}: widest deviation in units of the "
          f"tolerance: {json.dumps(units_of(got, want))}", flush=True)
    return [want[k] for k in OUTPUTS + PRECISION]
