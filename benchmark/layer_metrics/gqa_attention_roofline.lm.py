"""Device programs, the attention kernels: the grouped-query attention's fused kernels' share of their
roofline, in the step program's operations of the traced span. The kernels'
seconds by name (``splash_mha_fwd*``, ``splash_mha_dq*``, ``splash_mha_dkv*``
inside the step program's ``XLA Modules`` events) against the least time the
chip could take for what the mathematics needs of them: the causal half of
two products a forward and five a backward
(``references/lfm2_moe.py::attention_kernel_flops``; the second forward call
of a step is the layer's recomputation, and each backward kernel makes the
scores again: neither is counted) over the chip's bf16 peak. Bound by
compute: at 8,192 tokens a head's q, k, v, o move 4 MB for 1.1 GFLOP.
Moves trials_per_hour."""


def read(m):
    k = m.get("attention_kernels")
    if not k or k["seconds"] <= 0.0 or m.get("peak") is None:
        return None
    return 100.0 * k["needed_flops"] / m["peak"]["bf16_flops"] / k["seconds"]
