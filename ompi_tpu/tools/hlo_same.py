"""hlo_same — are two trees' compiled programs one program?

    python -m ompi_tpu.tools.pallas_aot --only _step_1chip --dump A   # tree 1
    python -m ompi_tpu.tools.pallas_aot --only _step_1chip --dump B   # tree 2
    python -m ompi_tpu.tools.hlo_same A B

Compares the optimised HLO texts of the same name in two directories
(``pallas_aot --dump``) with what only describes the source removed: every
``metadata={...}``, the module's tables of file names, function names and
stack frames, which the metadata points into, and the source locations
inside each Mosaic kernel's serialized MLIR (a ``tpu_custom_call``'s
``body``: parsed and printed again without debug info).  What is left is
the program: instructions, shapes, layouts, schedule, kernels.  A change
that should touch names only (a ``jax.named_scope``, a moved line, a
renamed function) leaves it EQUAL; where the instruction names alone
differ (JAX lowers a function traced under another name stack a second
time, which moves the numbers) it says so and compares them renumbered in
order of appearance.  Exit code 0 if every pair is equal."""
from __future__ import annotations

import base64
import hashlib
import os
import re
import sys

_TABLES_RE = re.compile(
    r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*")
_METADATA_RE = re.compile(r",? ?metadata=\{[^}]*\}")
_BODY_RE = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_NAME_RE = re.compile(r"%[\w.\-]+")
_bodies: dict = {}


def kernel_text(b64: str) -> str:
    """A Mosaic kernel's MLIR, without its locations, from the ``body``
    of its ``custom-call``'s backend configuration."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        return str(ir.Module.parse(base64.b64decode(b64)))


def _kernel(b64: str) -> str:
    """A digest of a Mosaic kernel's MLIR without its locations."""
    if b64 not in _bodies:
        _bodies[b64] = hashlib.sha256(kernel_text(b64).encode()).hexdigest()
    return _bodies[b64]


def stripped(text: str) -> str:
    """The program of an optimised HLO text, without its source's names."""
    text = _METADATA_RE.sub("", _TABLES_RE.sub("\n", text))
    return _BODY_RE.sub(lambda m: f'"body":"mlir:{_kernel(m.group(1))}"',
                        text)


def renumbered(text: str) -> str:
    names: dict = {}
    return _NAME_RE.sub(
        lambda m: names.setdefault(m.group(0), f"%n{len(names)}"), text)


def compare(a: str, b: str) -> str:
    """``EQUAL``, ``EQUAL but for instruction names`` or ``DIFFERENT``."""
    a, b = stripped(a), stripped(b)
    if a == b:
        return "EQUAL"
    if renumbered(a) == renumbered(b):
        return "EQUAL but for instruction names"
    return "DIFFERENT"


def main(argv=None) -> int:
    one, two = (argv if argv is not None else sys.argv[1:])[:2]
    names = sorted(set(os.listdir(one)) & set(os.listdir(two)))
    same = bool(names)
    for name in names:
        with open(os.path.join(one, name), encoding="utf-8") as f:
            a = f.read()
        with open(os.path.join(two, name), encoding="utf-8") as f:
            b = f.read()
        verdict = compare(a, b)
        digest = hashlib.sha256(stripped(a).encode()).hexdigest()[:16]
        print(f"{name}: {verdict} ({len(stripped(a).splitlines())} lines, "
              f"{len(_BODY_RE.findall(a))} kernel bodies, sha256 {digest})")
        same = same and verdict.startswith("EQUAL")
    if not names:
        print(f"no file of one name in {one} and {two}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
