"""device_ms_per_frame: device busy time per answered frame in the
traced window, the union of the device operations' intervals over the
frames answered, in ms."""


def read(obs):
    return obs.device_ms_per_frame()
