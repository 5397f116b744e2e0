"""Call kind ``allreduce_init``: the persistent handle
``world.allreduce_array_init(x, op)`` returns (MPI_Allreduce_init), then
called like a function.  Binding runs the collective once.  Everything
but the binding is ``allreduce``'s, the file beside this one."""
import os

from harness import collkit
from harness.protocol import load_module

_base = load_module("kinds", "allreduce",
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COLLECTIVES_PER_CALL = _base.COLLECTIVES_PER_CALL
ELEMENTWISE_LAST_AXIS = _base.ELEMENTWISE_LAST_AXIS
bind_raw = _base.bind_raw
bus_bytes = _base.bus_bytes
input_shape = _base.input_shape
input_sharding = _base.input_sharding
moved_bytes = _base.moved_bytes
reference = _base.reference


def bind(env, point, template):
    handle = env.world.allreduce_array_init(template, collkit.op_of(point))
    return handle, 1
