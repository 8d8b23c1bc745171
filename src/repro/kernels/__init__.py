# Pallas TPU kernels for the compute hot-spots the paper itself optimizes:
#   ce_softmax     — streaming fused softmax-CE over a vocab shard (§3.2's
#                    ">80% of the time" softmax stage; fwd + bwd)
#   sparse_ce      — fused active-class gather + CE (dynamic class
#                    selection; knn / selective / sampled candidate sets)
#   knn_dist_topk  — fused distance + running top-k' (graph build §3.2.2)
#   topk_dc        — divide-and-conquer top-k stage 1 (Fig. 5; DGC + top-k
#                    serving)
#   ivf_rerank     — fused row gather + top-k over IVF candidates (serving)
#   ops            — jit'd public wrappers + custom VJPs (the only module
#                    the rest of the repo imports)
#   ref            — pure-jnp oracles for the tests
# Heads select this path with HeadConfig.backend="pallas"; docs/kernels.md
# has the inventory, the VJP seam, and interpret mode vs the chip.
