"""Pallas kernels for the paper's sparse hot spots, their jnp oracles
(``ref.py``) and jitted wrappers (``ops.py``).

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
