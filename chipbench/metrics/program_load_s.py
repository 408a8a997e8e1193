"""program_load_s: seconds to ready the cell's frame programs, one per
(bucket, B), as `DetectionSession.warmup` counts them
(repro.core.detector.program_loads: compile or persistent-cache load
plus the first run), summed: the part of setup_s the program's size and
count decide. None where the program keeps no such counter."""
from chipbench import stages


def read(obs):
    return stages.program_load_s(obs)
