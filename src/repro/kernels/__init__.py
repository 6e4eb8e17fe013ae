"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package ships three files:
  kernel.py — pl.pallas_call + BlockSpec VMEM tiling (TPU target; validated
              with interpret=True on CPU),
  ops.py    — the jit'd public wrapper with shape padding + fallbacks,
  ref.py    — the pure-jnp oracle the tests assert against.

Kernels:
  knn_topk      — fused pairwise-distance + online top-k (Stage 1 hot op:
                  device-resident kNN graph construction, no n×n matrix).
  lsh_candidates— random-hyperplane LSH hashing + candidate windowing (the
                  approximate Stage-1 front-end; candidates feed the exact
                  knn_topk_rerank, O(n²d) → O(n·m·d)).
  kmeans_assign — fused pairwise-distance + online argmin (Stage 3 hot op).
  coo_spmv      — row-sorted COO SpMV in one pass: x in VMEM, gathered
                  in registers, rows reduced in the kernel (Stage 2 hot op,
                  single vector; DESIGN.md §19).
  ell_spmv      — blocked-ELL SpMV (Stage 2, single vector).
  ell_spmm      — blocked-ELL multi-vector SpMM (Stage 2 hot op in block-
                  Lanczos mode: one nnz stream serves b Krylov vectors).
"""
