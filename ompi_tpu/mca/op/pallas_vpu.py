"""op/pallas_vpu — Pallas VPU reduction kernels (the op/avx analog).

Reference: ``ompi/mca/op/avx/op_avx_component.c`` registers with a high
priority and per-type flag checks against the host CPU's capabilities;
here the capability check is the devices' platform (TPU: compiled Mosaic
kernels; elsewhere the kernels still work via the Pallas interpreter but
plain XLA is just as good, so priority drops below op/xla off-TPU).
"""
from __future__ import annotations

from ompi_tpu.base import mca
from ompi_tpu.base.jaxenv import pallas_interpret
from ompi_tpu.ops import pallas_reduce


class PallasVpuComponent(mca.Component):
    name = "pallas_vpu"
    priority = 50

    def register_vars(self, fw) -> None:
        self._prio_var = self.register_var(
            "priority", vtype=mca.VarType.INT, default=50,
            help="Selection priority of the Pallas VPU reduction kernels")

    def open(self) -> bool:
        self.priority = int(self._prio_var.value)
        if pallas_interpret():
            # interpreter mode works but wins nothing; defer to op/xla
            self.priority = min(self.priority, 5)
        return True

    def close(self) -> None:
        from ompi_tpu.mca.op import base as op_base

        op_base.reset_cache()

    def query_fold(self, op_name: str, dtype, fusable: bool = False):
        if fusable:
            return None  # pallas_call is opaque to XLA fusion
        return pallas_reduce.device_fold(op_name, dtype)

    def query_stack(self, op_name: str, dtype):
        if pallas_reduce.device_fold(op_name, dtype) is None:
            return None
        import functools

        return functools.partial(pallas_reduce.reduce_stack, op_name)


COMPONENT = PallasVpuComponent()
