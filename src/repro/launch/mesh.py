"""Mesh builders. Functions (not module constants) so importing never
touches jax device state: the device count is fixed only at first jax
init (repro.platform may force host devices before it).

`make_detection_mesh` is the detection-side default: the sharded
detect_batch path (core/detector.py) lays its frame batch over the
1-D 'data' axis of this mesh, one B/n_devices sub-batch per chip.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_host_mesh(model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    if not 1 <= model <= n:
        # without this guard, model > n makes data = n // model == 0 and
        # the reshape below dies with an opaque numpy size-mismatch error
        raise ValueError(
            f"make_host_mesh(model={model}): the host has {n} visible "
            f"device(s) (jax.devices()); 'model' must be in [1, {n}]")
    data = n // model
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def make_detection_mesh(data_parallel: int = 0) -> Mesh:
    """1-D 'data' mesh for sharded detection -- the detection default.

    `data_parallel=0` takes every visible device (the host-mesh data
    axis with model=1); `n > 0` takes exactly the first n devices and
    raises a clear ValueError when the host has fewer. The sharded
    detect_batch program (core/detector.py:_sharded_batch_fn) shards
    its frame batch over this mesh's 'data' axis.
    """
    n = len(jax.devices())
    data = n if data_parallel == 0 else int(data_parallel)
    if not 1 <= data <= n:
        raise ValueError(
            f"make_detection_mesh(data_parallel={data_parallel}): the "
            f"host has {n} visible device(s) (jax.devices()); "
            f"data_parallel must be 0 (= all) or in [1, {n}]")
    return Mesh(np.asarray(jax.devices()[:data]), ("data",))


def make_tiled_mesh(data_parallel: int = 1, frame_parallel: int = 0) -> Mesh:
    """2-D ('data', 'tile') mesh for intra-frame tiled detection.

    The frame batch is sharded over 'data' (as in make_detection_mesh)
    and each frame's pyramid work is split over 'tile' -- the tiled
    detect programs (core/detector.py:_tiled_single_fn /
    _tiled_batch_fn) run their per-tile local top-k under shard_map on
    this mesh. `frame_parallel=0` takes every device left over after
    the data axis; single-frame tiled latency uses data_parallel=1 with
    'tile' spanning the host (DESIGN.md §11).
    """
    n = len(jax.devices())
    dp = n if data_parallel == 0 else int(data_parallel)
    if dp < 1 or dp > n:
        raise ValueError(
            f"make_tiled_mesh(data_parallel={data_parallel}): the host "
            f"has {n} visible device(s) (jax.devices()); data_parallel "
            f"must be 0 (= all) or in [1, {n}]")
    fp = (n // dp) if frame_parallel == 0 else int(frame_parallel)
    if fp < 1 or dp * fp > n:
        raise ValueError(
            f"make_tiled_mesh(data_parallel={data_parallel}, "
            f"frame_parallel={frame_parallel}): with {n} visible "
            f"device(s) and data_parallel={dp}, frame_parallel must be "
            f"0 (= all remaining) or in [1, {n // dp}]")
    devs = np.asarray(jax.devices()[: dp * fp]).reshape(dp, fp)
    return Mesh(devs, ("data", "tile"))
