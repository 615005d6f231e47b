"""Pallas TPU kernels for the DPPS per-round hot spots.

The DPPS protocol's per-round tensor work is pointwise-plus-reduction over
the shared parameters: perturb, draw Laplace noise, add it, and produce the
two L1 norms the sensitivity recursion needs. Unfused, that is ~6 HBM
round-trips over d_s elements; the ``dpps_perturb`` kernel does it in one
read + one write with on-chip (VMEM) accumulation of the norms.

Kernels (each: <name>.py with pl.pallas_call + BlockSpec; ops.py jit'd
wrappers; ref.py pure-jnp oracles):

* laplace_noise   — u32 bits -> Laplace(0, scale) via inverse CDF
* l1_clip         — tiled L1-norm reduce + clip-scale (paper Eq. 24)
* dpps_perturb    — fused s + eps + gamma_n * Lap(bits) with norm accumulators
* pushsum_mix     — W @ s_tile circulant/dense mixing block (MXU-shaped)
* flash_attention — blockwise online-softmax causal/sliding-window GQA
                    forward (targets the memory-bound 32k prefill rows in
                    EXPERIMENTS.md SRoofline; O(S*D) HBM traffic vs O(S^2))

TPU PRNG note: on real TPUs the bits could come from pltpu.prng_random_bits
inside the kernel; CPU interpret mode (the test path) cannot lower that
primitive, so bits are generated with jax.random.bits and passed in — the
fusion structure (single pass over d_s) is unchanged.

Mosaic constraints the kernels are written to (tests/test_tpu_compile.py
compiles each one for a v5e chip): per-grid-step partial sums leave as
lane-aligned (8, 128) blocks and are summed outside the kernel; scalars
ride in 2-D SMEM operands; bits become floats through int32. Interpret mode
is chosen only by ``ops.default_interpret`` (from the platform).
"""
