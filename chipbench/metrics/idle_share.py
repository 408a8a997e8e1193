"""idle_share: share of the traced window in which no operation ran on
the device, in %."""


def read(obs):
    return obs.idle_share()
