"""Decode attention kernel: least time of every ``flash_decode_paged``
call in the trace (bf16 peak, HBM bandwidth) over the time the calls
took, in percent."""

import counts


def read(obs):
    calls = obs.trace.kernel_calls("flash_decode_paged") if obs.trace else []
    if not calls:
        return None
    pk = counts.peaks(obs.device_kind)
    least = sum(counts.least_time(*counts.flash_decode_paged(out, opr),
                                  pk["bf16_flops_per_s"],
                                  pk["hbm_bytes_per_s"])
                for out, opr, _ in calls)
    return 100.0 * least / sum(sec for _, _, sec in calls)
