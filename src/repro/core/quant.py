"""Quantizers — BMXNet §2.1 (Eq. 1), §2.2 (binarization), §2.2.2 (Eq. 2).

All quantizers are straight-through-estimator (STE) functions: forward is the
discrete map, backward passes the gradient through (clipped for sign, as in
XNOR-Net / BinaryConnect, which BMXNet follows).

``act_bit`` semantics follow the paper exactly:
  * 32      -> identity (full precision)
  * 1       -> binarization with ``sign`` into {-1, +1}
  * 2..31   -> DoReFa linear quantization (Eq. 1) on the appropriate range
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FULL_PRECISION = 32


def _ste(x: jax.Array, q: jax.Array) -> jax.Array:
    """Forward ``q``, gradient of identity w.r.t. ``x``."""
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def sign_ste(x: jax.Array) -> jax.Array:
    """sign into {-1,+1} with sign(0)=+1; clipped STE: dy/dx = 1[|x|<=1]."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _sign_fwd(x):
    return sign_ste(x), x


def _sign_bwd(x, g):
    return (g * (jnp.abs(x) <= 1.0).astype(g.dtype),)


sign_ste.defvjp(_sign_fwd, _sign_bwd)


def quantize_k(x: jax.Array, k: int) -> jax.Array:
    """Paper Eq. 1: quantize ``x`` in [0,1] onto the k-bit grid, with STE.

        quantize(input, k) = round((2^k - 1) * input) / (2^k - 1)
    """
    n = float(2**k - 1)
    return _ste(x, jnp.round(x * n) / n)


def _act_unit(x: jax.Array) -> jax.Array:
    """DoReFa activation pre-transform: clip into the [0, 1] grid domain.
    Shared by :func:`quantize_act` and :func:`act_codes` so the fake-quant
    values and the packed integer codes cannot drift."""
    return jnp.clip(x, 0.0, 1.0)


def _weight_unit(w: jax.Array) -> jax.Array:
    """DoReFa weight pre-transform: ``tanh(w)/(2 max|tanh(w)|) + 1/2`` into
    [0, 1].  The max runs over the WHOLE tensor.  Shared by
    :func:`quantize_weight` and :func:`weight_codes` (same no-drift rule)."""
    t = jnp.tanh(w)
    return t / (2.0 * jnp.max(jnp.abs(t)) + 1e-12) + 0.5


def quantize_act(x: jax.Array, bits: int) -> jax.Array:
    """QActivation: binarize (1 bit) or DoReFa-quantize activations.

    1 bit  -> sign(x) in {-1,+1}   (xnor-compatible)
    k bits -> quantize_k(clip(x, 0, 1), k)   (DoReFa activation quantizer)
    32     -> identity
    """
    if bits >= FULL_PRECISION:
        return x
    if bits == 1:
        return sign_ste(x)
    return quantize_k(_act_unit(x), bits)


def quantize_weight(w: jax.Array, bits: int) -> jax.Array:
    """Weight quantizer used by QConvolution / QFullyConnected.

    1 bit  -> sign(w) in {-1,+1}
    k bits -> DoReFa: 2 * quantize_k(tanh(w)/(2 max|tanh(w)|) + 1/2, k) - 1
    32     -> identity
    """
    if bits >= FULL_PRECISION:
        return w
    if bits == 1:
        return sign_ste(w)
    return 2.0 * quantize_k(_weight_unit(w), bits) - 1.0


# ---------------------------------------------------------------------------
# Integer-code views of the DoReFa quantizers — the packed k-bit serving
# path (kernels/kbit_gemm.py) stores bit-plane stacks of these codes.  Both
# share the pre-transforms (_act_unit / _weight_unit) with the float
# quantizers and round the SAME product, so the codes and the fake-quant
# values cannot drift; tests assert quantize_act(x, k) ==
# act_codes(x, k) / (2^k - 1) and the weight analogue.
# ---------------------------------------------------------------------------


def act_codes(x: jax.Array, bits: int) -> jax.Array:
    """DoReFa activation codes: ``round(clip(x, 0, 1) * (2^bits - 1))`` as
    uint32 in [0, 2^bits - 1].  ``quantize_act(x, bits) == codes / n``.

    Its arithmetic (:func:`act_levels`) is also called INSIDE the fused
    quantize->pack Pallas prologue
    (``kernels/pack_bits.quant_pack_planes_pallas``) on each VMEM tile —
    pure elementwise jnp, so it traces in a kernel body — which is what
    guarantees the fused serving prologue and this jnp reference cannot
    drift.  Note x <= 0 (the dispatch layer's float pad value is
    -1.0) maps to code 0: all plane bits 0, contributing nothing to the
    plane GEMM or the row-sums."""
    return act_levels(x, bits).astype(jnp.uint32)


def act_levels(x: jax.Array, bits: int) -> jax.Array:
    """The DoReFa activation code as a float level in [0, 2^bits - 1] — the
    arithmetic :func:`act_codes` casts.  The fused Pallas prologue casts it
    to int32 instead (Mosaic has no float -> uint32 cast)."""
    n = float(2**bits - 1)
    return jnp.round(_act_unit(x) * n)


def weight_codes(w: jax.Array, bits: int) -> jax.Array:
    """DoReFa weight codes (uint32 in [0, 2^bits - 1]):

        quantize_weight(w, bits) == (2 * codes - n) / n,  n = 2^bits - 1.

    ``_weight_unit``'s global max runs over the WHOLE tensor, so callers
    must pass the same tensor extent the training path quantizes (e.g. the
    full MoE expert stack, not one expert)."""
    n = float(2**bits - 1)
    return jnp.round(_weight_unit(w) * n).astype(jnp.uint32)


def weight_scale(w: jax.Array, axis: int = 0) -> jax.Array:
    """Per-output-channel alpha = mean|W| (XNOR-Net style, optional in BMXNet).

    ``axis`` is the contraction (input) axis of the weight.
    """
    return jnp.mean(jnp.abs(w), axis=axis, keepdims=True)


def xnor_range_map(dot: jax.Array, n: int) -> jax.Array:
    """Paper Eq. 2: map a ±1 dot product in [-n, n] (step 2) to the
    xnor+popcount count in [0, n] (step 1): out = (dot + n) / 2."""
    return (dot + n) / 2


def dot_range_map(counts: jax.Array, n: int) -> jax.Array:
    """Inverse of Eq. 2: xnor match count -> ±1 dot product."""
    return 2 * counts - n
