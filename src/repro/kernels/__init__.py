"""Pallas TPU kernels for the compute hot-spots, each with a pure-jnp
oracle in ref.py and a jit'd wrapper in ops.py (interpret mode off-TPU):

* varco_pack       — the paper's compression pack/unpack (lane-block
                     gather/scatter steered from SMEM scalar prefetch)
* flash_attention  — causal / sliding-window online-softmax attention (GQA)
* ell_spmm         — ELLPACK neighbour aggregation (GNN eq. 2), forward
                     only; training aggregates by row gathers
                     (``ops.ell_aggregate``)
* ssd_chunk        — Mamba2 SSD intra-chunk quadratic form
"""
