"""Decode attention in the prefill windows: device milliseconds of the
ops under the model scope ``attn`` per execution of the jitted chunked-
prefill window (``jit__window``), from the trace."""

import spantrace


def read(obs):
    sp = spantrace.of(obs)
    return sp.scope_ms("jit__window", "attn") if sp else None
