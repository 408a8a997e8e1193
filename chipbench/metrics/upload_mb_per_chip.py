"""upload_mb_per_chip: MB that the tiled path's upload placed on its
busiest chip per frame, in MB (1e6 bytes), from the detector's counter
(repro.core.detector.tiled_uploads: the bytes put on each device and the
frames uploaded, process-wide). The counter also holds the set-up's
frames; every frame of a cell has one size, so they weigh as the
window's do. None where the program keeps no such counter or uploaded
no tiled frame."""


def read(obs):
    try:
        from repro.core.detector import tiled_uploads
    except ImportError:
        return None
    up = tiled_uploads()
    if not up["frames"] or not up["bytes"]:
        return None
    return max(up["bytes"].values()) / up["frames"] / 1e6
