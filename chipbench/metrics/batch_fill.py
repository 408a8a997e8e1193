"""batch_fill: frames per dispatched batch over the service's batch
target, over the window (engine counters), in %."""


def read(obs):
    batches = obs.engine.get("frame_batches", 0)
    if not batches:
        return None
    return 100.0 * obs.engine["frames"] / (batches * obs.engine["frame_target"])
