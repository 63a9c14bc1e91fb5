"""Device: share of the traced interval of a serving cell in which no
jitted program ran on the chip, in percent."""


def read(obs):
    t = obs.trace
    return 100.0 * (1 - t.busy_s / t.window_s) if t else None
