"""batch_idle_share: share of the traced window in which the service's
worker held a batch (its `serve.batch` spans, repro.obs.spans) and no
operation ran on the device, in %: the device waiting on the host's
gather, stack, upload, dispatch, slicing, fetch and decode. The window's
batches are the last `frame_batches` spans the program recorded; every
device operation of the window runs inside one of them, so the share is
their time less the device's busy time, over the window
(chipbench/stages.py). None where the program records no spans."""
from chipbench import stages


def read(obs):
    return stages.batch_idle_share(obs)
