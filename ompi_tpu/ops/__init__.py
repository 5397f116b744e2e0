"""TPU compute kernels (Pallas) for the framework's hot ops.

The reference keeps its SIMD reduction kernels in an MCA op component
(``ompi/mca/op/avx/op_avx_functions.c`` — AVX2/AVX-512 sum/min/max/...);
the TPU analog is Pallas kernels driving the VPU (elementwise reductions)
and MXU (attention blocks).  The MCA ``op`` framework
(``ompi_tpu/mca/op/``) selects these when running on a TPU backend and
falls back to plain XLA (jnp) elsewhere, mirroring the reference's
runtime CPU-capability dispatch (``op_avx_component.c``).

A public model's train step (``parallel/``) chooses its own kernels the
same way, from what it can see (``interpret`` false: a TPU, and a shape
with tiles), each beside the XLA form that is its oracle:
``flash_attention`` (causal attention, forward and backward),
``grouped_matmul`` (the experts' products), ``gated_delta`` (the chunked
gated delta rule), ``causal_conv`` (the DeltaNet convolution and its
silu), ``sparse_attention`` (DSA's index selection and alignment loss),
``head_norm_rope`` (a head's norm where it has one, RoPE, split and cast
on its way to the flash kernels) and ``row_scatter`` (the held experts' loop's
rows added into the layer's sums by token).
"""
from ompi_tpu.ops.pallas_reduce import (  # noqa: F401
    combine2,
    reduce_stack,
    supported_ops,
)
