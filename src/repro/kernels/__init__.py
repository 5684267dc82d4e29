"""Pallas TPU kernels for the compute hot-spots the paper builds silicon for.

  fourstep_fft      — the paper's heterogeneous FFT-A(256)xFFT-B(128)
                      cluster, recast as MXU matmuls (four-step FFT).
  external_product  — the BRU transform-domain MAC with round-robin
                      (batched) BSK reuse.
  keyswitch         — the LPU key-switch MAC; 64-bit torus arithmetic
                      synthesized from uint32 limbs (TPU has no u64).
  fused_pbs         — the three kernels fused into the batched PBS hot
                      path with resident transform-domain keys; this is
                      what `TaurusEngine(kernel_backend="pallas")` runs.

Each kernel ships jit wrappers in `ops.py` and a pure-jnp oracle in
`ref.py`; tests sweep shapes/dtypes in interpret mode and grade the
fused path differentially against the reference engine, and
`tests/test_tpu_compile.py` compiles each kernel for a described v5e.
"""
import jax


def interpret_mode() -> bool:
    """Pallas interpret mode follows the platform: on for CPU, which has
    no Mosaic compiler, and off on a TPU, where the kernels compile for
    real.  Every kernel entry resolves `interpret=None` through this."""
    return jax.default_backend() == "cpu"
