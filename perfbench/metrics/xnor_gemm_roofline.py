"""Packed GEMM kernels: the least time of every packed-GEMM call
(``xnor_mismatch_pallas``, held to the int8 peak) and of its activation
pack prologue (``pack_sign_pallas``, bytes) in the trace, over the time
those calls took, in percent."""

import counts


def read(obs):
    if obs.trace is None:
        return None
    pk = counts.peaks(obs.device_kind)
    least = took = 0.0
    for kernel, fn in (("xnor_mismatch_pallas", counts.xnor_gemm),
                       ("pack_sign_pallas", counts.pack_sign)):
        for out, operands, sec in obs.trace.kernel_calls(kernel):
            ops, nbytes = fn(out, operands)
            least += counts.least_time(ops, nbytes, pk["int8_ops_per_s"],
                                       pk["hbm_bytes_per_s"])
            took += sec
    return 100.0 * least / took if took else None
