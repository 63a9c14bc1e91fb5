"""Model step: model operations of the real tokens processed in the traced
interval (prompt tokens prefilled and tokens decoded, each through every
GEMM and attention over its context, plus the head for each emitted
token), per second of the interval, over the chip's int8 peak, in
percent."""

import counts


def read(obs):
    if obs.trace is None or not obs.counters["seconds"]:
        return None
    c, d = obs.counters, obs.dims
    ops = sum(counts.token_ops(d, p + 1) for p in c["prefill_pos"])
    ops += sum(counts.token_ops(d, p + 1) for p in c["decode_ctx"])
    ops += c["emitted"] * counts.head_ops(d)
    peak = counts.peaks(obs.device_kind)["int8_ops_per_s"]
    return 100.0 * ops / c["seconds"] / peak
