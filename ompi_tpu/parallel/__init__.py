"""ompi_tpu.parallel — first-class ML-parallelism toolkit over the mesh.

The reference is the communication substrate *under* ML parallelism
(SURVEY.md §2.6): DP/TP/PP/SP/EP are what users build on MPI.  Here they
are first-class: a 4-axis ``Mesh`` (dp, pp, sp, tp) with

- **dp**  — data parallel gradient sync (``psum`` ≅ allreduce ring,
  ``coll_base_allreduce.c:341``)
- **pp**  — pipeline stage handoff (``ppermute`` ≅ pml send/recv between
  stage ranks, ``pml_ob1_isend.c:233``)
- **sp**  — sequence/context parallelism: ring attention over a
  ``ppermute`` ring (the segmented-ring pipeline shape,
  ``coll_base_allreduce.c:618``)
- **tp**  — tensor parallel matmuls with ``psum`` combine; the same axis
  carries **ep** (MoE expert parallel) via ``all_to_all`` dispatch
  (≅ ``coll_base_alltoall.c`` pairwise exchange)

All four axes run in the invented step of ``flagship.py``; a public
model's step (``train.build_train_step``, exported here) shards over dp.
"""
from ompi_tpu.parallel.mesh import MeshSpec, make_mesh, default_axis_sizes
from ompi_tpu.parallel.train import build_train_step

__all__ = [
    "MeshSpec", "make_mesh", "default_axis_sizes", "build_train_step",
]
