"""Pallas kernels, their jnp oracles (``ref.py``) and wrappers (``ops.py``):
the block-sparse masked matmul of the serving launch (``masked_matmul.py``)
and the packed-payload accumulate (``packed_accum.py``), which the TPU
compiler refuses and no default path runs.

Every ``pallas_call`` takes its mode from ``_interpret()``: compiled
(Mosaic) on a TPU, the Pallas interpreter on the CPU so tests run the
same kernel bodies there, and an error anywhere else.
"""
import jax


def _interpret() -> bool:
    """Pallas interpret mode for the default backend: False on ``tpu``,
    True on ``cpu``; no other platform runs these kernels."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu or interpreted on cpu; the "
        f"default backend is {platform!r}")
