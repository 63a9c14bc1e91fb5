"""Scheduler: share of decode-step rows that emitted a token, over the
traced interval (decode-emitted tokens over steps x slots)."""


def read(obs):
    c = obs.counters
    if not c["steps"]:
        return None
    return 100.0 * c["decode_tokens"] / (c["steps"] * c["slots"])
