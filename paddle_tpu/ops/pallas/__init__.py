"""Pallas TPU kernels — the analog of the reference's hand-written fused
CUDA kernels (operators/fused/, operators/math/bert_encoder_functor.cu).

The kernel gates (flash_attention.supported, fused_ops ln/bg/adam gates)
normally consult ``jax.default_backend()``; when CROSS-LOWERING a step for
TPU on a CPU host (jax.export ``platforms=("tpu",)`` — the chip-free
lowering census, tests/test_pallas_tier.py), wrap the trace in
``lowering_target("tpu")`` so the gates see the *lowering* platform rather
than the runtime backend."""

import contextlib

import jax

_LOWERING_TARGET = None


@contextlib.contextmanager
def lowering_target(platform: str):
    """Override the backend the Pallas kernel gates see for the duration
    of a trace (e.g. ``with lowering_target("tpu"): jax.export(...)``)."""
    global _LOWERING_TARGET
    prev = _LOWERING_TARGET
    _LOWERING_TARGET = platform
    try:
        yield
    finally:
        _LOWERING_TARGET = prev


def effective_backend() -> str:
    """The platform kernels are being lowered for: the explicit
    lowering_target if one is active, else the runtime default backend.
    A backend that fails to start raises here — routing every kernel to
    its jnp reference would hide the missing device."""
    if _LOWERING_TARGET is not None:
        return _LOWERING_TARGET
    return jax.default_backend()


def is_tpu_backend(backend=None) -> bool:
    """Is ``backend`` (default: the effective lowering backend) the one
    the Pallas kernels compile for (the TPU Mosaic pipeline)?"""
    return (backend or effective_backend()) == "tpu"
