# Pallas TPU kernels for the paper's compute hot spots (distance evaluation
# is >=83% of ANNS query time — Fig. 2).  Each kernel: <name>.py (pallas_call
# + BlockSpec), validated in interpret mode against ref.py oracles and
# compiled for a v5e in tests/test_tpu_compile.py; ops.py is the jit'd public
# wrapper layer (padding, kernel table layouts, interpret only on CPU).
#
#   l2_distance.py      tiled distance matrix (MXU)           [brute force/KNN/DLRM retrieval]
#   crouting_prune.py   fused cosine-estimate + prune (VPU)   [paper Alg. 2 inner loop]
#   gather_distance.py  masked row-DMA gather + distance (shared DMA loop)
#   pool_merge.py       bitonic sorted-pool merge (VPU network)
#   fused_expand.py     estimate + prune + conditional gather + distance in
#                       one kernel — the beam engine's per-iteration tile op
#                       (core/search.py, SearchSpec.engine="pallas")
#   sq8_distance.py     uint8 code-row gather + dequantized distance +
#                       conservative lower bound — stage 1 of the two-stage
#                       engine (SearchSpec.estimate="sq8"|"both")

from repro.kernels import ops  # noqa: F401
