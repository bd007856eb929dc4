"""Pallas TPU kernels of the hybrid pipeline: the dense input core
(`dense_conv_lif`), the occupancy-gated sparse cores (`spike_conv`), the LIF
epilogue (`lif_step`), plus the LM family's `flash_attention` and
`int4_matmul`. Each package holds the kernel, a jitted `ops.py` wrapper with
an ``interpret`` keyword, and a pure-jnp `ref.py` oracle."""
import jax

#: Precision of the kernels' float32 matmuls. Mosaic's default rounds the
#: operands to bf16 (one MXU pass): on a v5e a 640-deep product of O(1)
#: values was off by 1.7e-2, against 1.3e-6 with HIGHEST. The model is
#: float32, so the kernels ask for it.
F32_DOT = jax.lax.Precision.HIGHEST


def interpret_mode() -> bool:
    """Whether the model-level paths run their kernels in the Pallas
    interpreter: everywhere but on a TPU, where they lower through Mosaic.
    Read at trace time, so the choice follows the backend that compiles."""
    return jax.default_backend() != "tpu"
