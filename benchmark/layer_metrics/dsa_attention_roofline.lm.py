"""Device programs, the sparse attention kernels: their share of the
roofline of the work the mechanism needs, in the step program's operations
of the traced span. The kernels' seconds by name (``dsa_fwd*``, ``dsa_dq*``,
``dsa_dkv*`` inside the step program's ``XLA Modules`` events) against the
least time the chip could take for what the selected attention needs,
whatever implements it (``references/keye_vl2.py::sparse_attention_work``):
4 x heads x head width x sum_t |S_t| FLOPs forward and 2.5 times that
backward; q, k, v and o read or written once forward, and those with their
gradients backward, in bfloat16; the larger of the FLOPs over the chip's
bf16 peak and the bytes over its HBM bandwidth. The second forward call of a
step is the layer's recomputation and is not counted; neither are the keys a
tile reads that no query of it selected, nor the indexer's scores and loss
the kernels compute beside the attention. Moves trials_per_hour."""


def read(m):
    k = m.get("dsa_kernels")
    if not k or k["seconds"] <= 0.0 or m.get("peak") is None:
        return None
    bound = max(k["needed_flops"] / m["peak"]["bf16_flops"],
                k["needed_bytes"] / m["peak"]["hbm_bytes_per_s"])
    return 100.0 * bound / k["seconds"]
