"""Pallas TPU kernels for the perf-critical compute of VQ-GNN.

Each kernel ships as <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), with jit'd dispatching wrappers in ops.py and pure-jnp oracles in
ref.py.  Kernels: vq_assign (fused distance+argmin), vq_update (fused
assign + cluster counts/sums + per-row quantization error -- the one-pass
streaming codebook update, no one-hot intermediate), spmm_ell (ELLPACK
message passing, VMEM-resident source), spmm_ell_hbm (ELLPACK message
passing, HBM-resident source gathered by row DMAs),
context_ell (one-pass multi-branch VQ-context SpMM -- Eq. 6 context
forward and streaming Eq. 7 backward, codebook VMEM-resident).
"""
