"""Pallas TPU kernels for the compute hot-spots, with pure-jnp oracles.

  lbp_matmul_kernel    layer-accumulating blocked matmul (the paper's layers
                       as K-grid steps with a VMEM accumulator)
  flash_attention_kernel  blocked online-softmax attention (KV blocks as layers)
  rglru_kernel         RG-LRU gated linear recurrence (recurrentgemma)
  paged_decode_attention_kernel  decode attention read in place from the
                       paged KV pool through the page table (one layer,
                       the live pages of each row only)
  slstm_kernel         weight-stationary sLSTM (recurrent R matrices VMEM-
                       resident across the time loop — kills the per-step
                       HBM weight re-reads that dominate xlstm's roofline)

ops.py holds the jit'd padded wrappers (off the TPU, ask for
interpret=True); ref.py the oracles; tests/test_kernels.py the
shape/dtype sweeps.
"""

from . import ops, ref  # noqa: F401
