"""What the two files of AOT compile-contract tests share
(``test_pallas_aot.py``, ``test_pallas_aot_cells.py``): the child that
compiles ``tools/pallas_aot``'s cases offline for a v5e, its rows with their
compiled texts, and the checks that more than one cell's step is held to.
The files are cut along the ``*_rows`` fixtures so that no fixture's child
runs in both.  The driver deals tests with ``--dist load``, runs of
consecutive items to whichever worker is free: ``conftest.py`` collects a
fixture's users side by side (the cross-cell cases name theirs in the
parameter ``rows``), and ``rows_with_texts`` keeps a child's rows for the
session (``built.shared``), so that a second worker that is dealt some of
a fixture's users reads them and compiles nothing.  Both files are dealt
first (``conftest.DEALT_FIRST``): their seconds are spent waiting for a
child, which is better done beside the whole run than at its end.
"""
import json
import os
import re
import subprocess
import sys
import tempfile

import pytest

import built

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: both files' ``pytestmark``
SKIP_AOT = pytest.mark.skipif(
    os.environ.get("OTPU_SKIP_AOT", "") not in ("", "0"),
    reason="AOT gate disabled by OTPU_SKIP_AOT")


def run_aot_subprocess(*extra, limit: int = 240, **env_extra) -> dict:
    """Run the AOT gate in a CPU-pinned subprocess: compile-only,
    bounded, and with the topology
    client's state kept out of the pytest process.  A lowering failure
    fails loudly from the result file."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    out = os.path.join(tempfile.mkdtemp(prefix="otpu_aot_"),
                       "pallas_aot.json")
    proc = subprocess.run(
        [sys.executable, "-m", "ompi_tpu.tools.pallas_aot",
         "--out", out, *extra],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=limit)
    if proc.returncode not in (0, 1) or not os.path.exists(out):
        raise RuntimeError(
            f"pallas_aot gate crashed (rc={proc.returncode}):\n"
            f"{proc.stderr[-1500:]}")
    with open(out) as f:
        return json.load(f)


def rows_with_texts(only: str, **env_extra) -> dict:
    """{case: its row, with ``hlo`` the file of its compiled text} of one
    child a session that compiles the cases named ``only`` for a v5e 2x2."""
    pytest.importorskip("libtpu")

    def compiled():
        dump = tempfile.mkdtemp(prefix="otpu_aot_hlo_")
        res = run_aot_subprocess("--only", only, "--topology", "v5e:2x2",
                                  "--dump", dump, limit=600, **env_extra)
        assert res.get("rows"), res.get("error")
        return {r["kernel"]: dict(r, hlo=os.path.join(
            dump, r["kernel"] + ".hlo.txt")) for r in res["rows"]}

    return built.shared("aot-" + "-".join(
        [only, *(f"{k}={v}" for k, v in sorted(env_extra.items()))]), compiled)


def kernel_bodies(hlo_text: str, prefix: str) -> dict:
    """{kernel name: its Mosaic module as MLIR text} of the compiled
    text's ``custom-call`` lines whose kernel is named ``prefix``..."""
    from ompi_tpu.tools import hlo_same

    out = {}
    for line in hlo_text.split("\n"):
        name = re.search(r"/(%s\w*)/pallas_call" % prefix, line)
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        if " custom-call(" in line and name and body:
            out[name.group(1)] = hlo_same.kernel_text(body.group(1))
    return out


def op_paths(row):
    """(line, ``op_name`` path) of every instruction of a row's compiled
    text that has one."""
    with open(row["hlo"], encoding="utf-8") as f:
        for line in f:
            if " = " in line and 'op_name="' in line:
                yield line, line.split('op_name="', 1)[1].split('"', 1)[0]


def fusion_operands(row, named: str, scope: str) -> dict:
    """{instruction: its fused computation's parameter types, ``f32[4,
    2048,8192]`` each} of a row's compiled text's fusions whose name holds
    ``named`` and whose ``op_name`` path holds ``scope``: what a product
    reads beside its epilogue (a stacked weight gradient's is
    ``dynamic-update-slice``: the slot written in place)."""
    with open(row["hlo"], encoding="utf-8") as f:
        text = f.read()
    headers = dict(re.findall(r"^%([\w.\-]+) \((.*)\) -> .* \{$", text,
                              re.M))
    out = {}
    for line, path in op_paths(row):
        made = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .* fusion\(.*"
                        r"calls=%([\w.\-]+)", line)
        if made and named in made.group(1) and scope in path:
            out[made.group(1)] = re.findall(r": ([a-z0-9]+\[[\d,]*\])",
                                            headers[made.group(2)])
    return out


def stacked_weight_gradients_read_what_was_made(row, scope: str,
                                               activation: str, n: int):
    """The feed-forward's backward pass written out (``layers.
    _ffn_backward``, PR 72): in the step compiled for a v5e the ``n``
    products under ``scope`` that write a slot of a stacked weight
    gradient in place read two bfloat16 arrays, the activation or a
    cotangent made once behind the rule's barrier and the rows, and none
    makes its operand anew from a float32 pre-activation
    (``activation``: ``f32[16384,8192]``)."""
    stacked = fusion_operands(row, "dynamic-update-slice", scope)
    assert len(stacked) == n, stacked
    for name, operands in stacked.items():
        assert activation not in operands, (name, operands)
        assert sum(o.startswith("bf16[") for o in operands) == 2 \
            and activation.replace("f32", "bf16") in operands, (name,
                                                                operands)


def fits_a_v5e(row) -> bool:
    """The most a compiled step holds at once (``memory_analysis()``'s
    ``peak_memory_in_bytes``: the arguments, which the donated state's
    results alias, and the temporaries alive at the worst moment) lies
    under a v5e's 15.75 GiB.  The sum of the arguments and
    ``temp_size_in_bytes`` bounds nothing the chip needs: JoyAI's step
    with o and the logsumexp kept reads 17.86 GB by it, compiles for the
    v5e and runs on one (peak 14.46 GB)."""
    return 0 < row["peak_bytes"] < 15.75 * 2 ** 30


def recomputed_pass_holds_no_routing(row):
    """A walked layer's checkpoint keeps what the expert block names
    (``experts.CHECKPOINT_KEEPS``, PR 43), so in the step compiled for a v5e
    no instruction under ``rematted_computation`` is a ``sort`` (the
    dispatch's argsort, and the top-k, which the TPU's compiler writes as
    a whole sort of (8192, E)), any other part of the top-k, the gather
    of the chosen scores (T k single entries: 1.8 ms a layer on the
    chip), the router's float32 product or the held experts' loop
    (``test_train_scopes.ROUTING``); they run in the forward pass, and a
    layer's other work is still recomputed.  The step fits the chip
    (``fits_a_v5e``)."""
    from test_train_scopes import ROUTING

    assert row.get("compiled"), json.dumps(row, indent=1)
    kinds = {"forward": set(), "remat": set()}
    recomputed = 0
    for line, path in op_paths(row):
        remat = "rematted_computation" in path
        recomputed += remat and "otpu_attn_proj" in path
        kinds["remat" if remat else "forward"].update(
            k for k, is_it in ROUTING.items() if is_it(line, path))
    assert recomputed > 20
    assert kinds == {"forward": set(ROUTING), "remat": set()}
    assert fits_a_v5e(row), json.dumps(row, indent=1)


def recomputed_pass_holds_no_attention_forward(row, calls):
    """A walked layer's checkpoint keeps causal attention's o and
    logsumexp (``model.CHECKPOINT_KEEPS``, PR 44), so in the step compiled
    for a v5e the forward kernel (``otpu_flash_causal_forward``) stands
    once a layer, in the forward pass, and nowhere under
    ``rematted_computation``: JoyAI's in the dense layer, in the body
    that the four sparse layers scan and in the module (six calls a
    step, twelve before), Nemotron's in its one attention layer; the
    backward kernel is where it was, and the step fits the chip
    (``fits_a_v5e``)."""
    assert row.get("compiled"), json.dumps(row, indent=1)
    kernels = [path for line, path in op_paths(row)
               if " custom-call(" in line]
    forward = sorted(p for p in kernels if "/otpu_flash_causal_forward/" in p)
    assert not [p for p in forward if "rematted_computation" in p]
    assert len(forward) == len(calls), forward
    for path, where in zip(forward, calls):
        assert path.startswith("jit(otpu_train_step)/" + where), path
    assert sum("/otpu_attn_block_backward/" in p
               for p in kernels) >= len(calls)
    assert fits_a_v5e(row), json.dumps(row, indent=1)
