"""Reader ``step_memory``: what the run's train steps need of one chip's
memory as compiled, as a share, in %, of the chip's ``hbm_bytes``
(``harness/peaks.json``).

The program can say what a step it built holds on a device
(``train.memory_of_built_steps()``: ``memory_analysis()`` of the step as
compiled, asked for after the measurement; it books nothing in the
program's build counters).  The largest step's ``peak_memory_in_bytes``
counts.  It reads the program, not points, so it takes no ``select`` and
no other parameter.  Prints the steps' fields (``memory [...]``).  A
program without the function, a run that built no step, or a compile that
fails: nothing to read."""
import json


def read(ctx, params):
    try:
        from ompi_tpu.parallel import train

        ask = train.memory_of_built_steps
    except (ImportError, AttributeError):
        return None             # the parent commit: no such function
    try:
        steps = ask()
    except Exception as e:      # a second compile that does not fit, ...
        print(f"memory: memory_of_built_steps() raised {e!r}", flush=True)
        return None
    if not steps:
        return None
    from harness import peaks

    print("memory " + json.dumps(steps), flush=True)
    return (100.0 * max(s["peak_memory_in_bytes"] for s in steps)
            / peaks.peaks(ctx["device_kind"])["hbm_bytes"])
