"""Pallas TPU kernels (compiled on a TPU, interpreted on the CPU).

dual_solve      — fused bandwidth best-response + gamma selection (solver)
topk_sparsify   — block-local magnitude top-k (the paper's compression)
score_norm      — fused sum-of-squares reduction (contribution score)
flash_attention — block-tiled causal/SWA GQA attention
ssd             — Mamba2's chunked SSD scan, forward and backward

Every ``ops.py`` wrapper asks ``interpret_mode()`` at call time, so the
same code runs the kernels compiled on a TPU and in the Pallas
interpreter on the CPU backend (tests, laptops). The kernel entry points
themselves default to compiled (``interpret=False``).
"""
import jax


def interpret_mode() -> bool:
    """True when the default backend is the CPU, where Pallas TPU kernels
    can only run in the interpreter; False on a TPU."""
    return jax.default_backend() == "cpu"


def sublanes(dtype) -> int:
    """Rows of one native VMEM tile for ``dtype``: 8 at 32 bits, 16 at
    16 bits, 32 at 8 bits. Row blocks of the row-tiled kernels are a
    multiple of this so they lower on the chip."""
    return max(8, 32 // jax.numpy.dtype(dtype).itemsize)
