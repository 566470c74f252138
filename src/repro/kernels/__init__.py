"""Pallas TPU kernels for the perf-critical compute of the assigned
architectures: flash attention (train/prefill), the mamba selective scan,
and fused RMSNorm.  (The paper itself contributes a search tool, not a
kernel; these kernels are the perf-critical substrate of the workloads the
framework runs, used by the beyond-paper perf pass.)

Each kernel directory holds:
  <name>.py -- the pl.pallas_call kernel with explicit BlockSpec VMEM tiling
               (``interpret`` is a required keyword of every ``*_fwd``)
  ops.py    -- the jit'd public wrapper (interpret mode on the CPU backend
               only, compiled Mosaic on the chip: :func:`interpret_mode`)
  ref.py    -- the pure-jnp oracle the tests assert against
"""


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on the CPU backend
    only.  Any other backend compiles them, so a kernel that cannot lower
    fails loudly instead of running interpreted on the device."""
    import jax
    return jax.default_backend() == "cpu"
