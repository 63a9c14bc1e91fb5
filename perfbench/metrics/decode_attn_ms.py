"""Decode attention: device milliseconds of the ops under the model scope
``attn`` (projections, ``attn/kv_write``, ``attn/attend``) per execution
of the jitted decode step (``jit__decode``), from the trace."""

import spantrace


def read(obs):
    sp = spantrace.of(obs)
    return sp.scope_ms("jit__decode", "attn") if sp else None
