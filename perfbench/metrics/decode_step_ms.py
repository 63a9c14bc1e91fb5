"""Model step: mean device time of one execution of the engine's jitted
decode step (``Engine._decode``), from the trace."""


def read(obs):
    runs = obs.trace.executions("jit__decode") if obs.trace else []
    return 1e3 * sum(runs) / len(runs) if runs else None
