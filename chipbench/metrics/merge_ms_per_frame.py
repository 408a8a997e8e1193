"""merge_ms_per_frame: device self time of the tiled frame program's
operations scoped `merge` (jax.named_scope in core/detector.py: the
gather of the tiles' top-k lists and their exact re-rank), over the
frames answered in the trace, in ms, summed over the chips. An
operation's scope is its `op_name` in the compiled text of the program
that served the window (repro.core.detector.op_scopes); this reader
matches the `merge` component of that path itself, so
chipbench/stages.py, which does not list it, counts these operations
as unscoped. None where the program carries no `merge` scope.

`stage_ms` is the reading of a scope that chipbench/stages.py lists, on
this cell: the `.uhd4` readers of the other stages read through it."""
from chipbench import stages

SCOPE = "merge"
#: what lowering an older detector's multi-chip program again raises:
#: it noted a one-device placement for the arguments it left
#: uncommitted, and a program over four devices refuses that placement
OLDER = "incompatible devices"


def _older(err: ValueError) -> bool:
    return OLDER in str(err)


def stage_ms(obs, name: str):
    """stages.stage_ms_per_frame(obs, name), or None where the program
    is an older detector's that cannot hand back its multi-chip scopes;
    every other error is raised."""
    try:
        return stages.stage_ms_per_frame(obs, name)
    except ValueError as e:
        if not _older(e):
            raise
        return None


def scoped(op_name: str) -> bool:
    """Whether `merge` is a component of an `op_name` path, transform
    wrappers looked through (`vmap(merge)`)."""
    for path in op_name.split(";"):
        for comp in path.split("/"):
            m = stages._WRAPPED.match(comp)
            while m:
                comp = m.group(1)
                m = stages._WRAPPED.match(comp)
            if comp == SCOPE:
                return True
    return False


def read(obs):
    if obs.trace is None or not obs.trace_frames or obs.trace.busy_s <= 0:
        return None
    try:
        scopes = stages.program_scopes(obs)
    except ValueError as e:
        if not _older(e):
            raise
        return None
    if scopes is None:
        return None
    ops = [op for op, name in scopes.items() if scoped(name)]
    if not ops:
        return None
    return 1e3 * sum(obs.trace.op_s.get(op, 0.0) for op in ops) \
        / obs.trace_frames
